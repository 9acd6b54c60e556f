"""A CPU rehearsal of a cell at tiny sizes, and a run that finds no TPU."""
import json

import pytest

from bench.tests.conftest import CELLS, bench_run

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_a_well_formed_last_line(cpu_env, trace, cell):
    env, pool = cpu_env
    p = bench_run(env, pool, "--workload", cell, "--seed", str(2**31 + 12345),
                  "--seconds", "2", "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    dev = line["device"]
    assert dev["platform"] == "cpu"
    # the device's peak counts the compiled step's scratch
    assert dev["step_temp_bytes"] > 0
    assert dev["memory_peak_bytes"] == dev["peak_bytes_in_use"] + dev["step_temp_bytes"]
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], float) and m["unit"]
    if trace == "0":
        assert set(line["metrics"]) == {"train_images_per_s", "step_p90_ms", "setup_s"}
    else:
        # device metrics come only from a chip's trace
        assert "step_device_ms" not in line["metrics"] and "cpu_ms_per_img" in line["metrics"]
    for name, c in line["checks"].items():
        assert f"check {name}:" in p.stderr


def test_no_tpu_fails_without_a_result(cpu_env):
    env, pool = cpu_env
    p = bench_run(env, pool, "--workload", "rn18.s3", "--seed", "1", "--seconds", "2",
                  "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
