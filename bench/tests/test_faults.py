"""With the timed path broken underneath, a rehearsed run reads not correct."""
import json

import pytest

from bench.tests.conftest import CELLS, bench_run


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault,number", [
    ("unchanged", "update_gap"),  # the step returns its state unchanged
    ("half", "grad_gap"),  # half of the batch left out
    ("alter", "batch_mismatch"),  # an answer altered where it is produced
])
def test_fault_reads_not_correct(cpu_env, fault, number, cell):
    env, pool = cpu_env
    p = bench_run(env, pool, "--workload", cell, "--seed", "77", "--seconds", "1",
                  "--trace", "0", "--rehearse", "--fault", fault)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    check = line["checks"][number]
    assert check["value"] > check["limit"]

