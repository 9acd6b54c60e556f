"""The lower-precision control -- the reference computed in bfloat16 in the
program's place -- reads not correct, while the program reads correct. The
control that keeps the ingest in float32 is read beside it."""
import json
import os
import subprocess
import sys

import pytest

from bench import check, spec
from bench.tests.conftest import CELLS, ROOT


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cpu_env, tmp_path, cell):
    env, pool = cpu_env
    out = tmp_path / "cal.jsonl"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "calibrate.py"), "--workload", cell,
         "--seeds", "5", "--seconds", "1", "--rehearse", "--pool-dir", pool, "--out", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    row = json.loads(out.read_text().splitlines()[0])
    limits = spec.resolve(cell, spec.load_benchmark()).limits
    assert check.judge(row["program"], limits)
    assert row["control_step"]["grad_gap_median"] > row["program"]["grad_gap_median"]
    for planted in ("control", "half", "unchanged"):
        assert not check.judge(row[planted], limits), (planted, row[planted])
