"""The reduction from a profiler trace to device numbers."""
import gzip
import json
import os

import pytest

from bench import trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "trace_rn18_s3.json.gz")


def test_union_intersect_and_gaps():
    assert trace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert trace.intersect([(0, 2), (3, 4)], [(1, 3.5)]) == [(1, 2), (3, 3.5)]
    assert trace.gaps_between([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert trace.total(trace.clip([(0, 10)], 2, 3)) == 1


def _toy():
    # one device; markers at trace ns 1000 and 11000 map to monotonic 50 s and
    # 50 s + 10 us; two steps of the train program, one ingest run between
    ns = 1
    return {
        "marks": [["bench:window_start", 1000 * ns, 1], ["bench:window_end", 11000 * ns, 1]],
        "devices": {"0": {
            "modules": [["jit_train_step(1)", 1000, 3000], ["jit_ingest(2)", 5000, 1000],
                        ["jit_train_step(1)", 7000, 3000]],
            "ops": [["fusion.1", 1000, 2000], ["conv.2", 2500, 1500],
                    ["pallas_ingest", 5000, 800],
                    ["fusion.1", 7000, 3000], ["all-reduce.3", 10500, 1000]],
        }},
    }


def test_toy_trace():
    t = trace.DeviceTrace(_toy(), 50.0, 50.0 + 10e-6)
    assert t.window_s() == pytest.approx(10e-6)
    # busy: 1000-4000, 5000-5800, 7000-10000, 10500-11000 (clipped) = 7300 ns
    assert t.busy_s() == pytest.approx(7.3e-6)
    steps = t.module_runs(r"train_step")
    assert len(steps) == 2
    assert t.op_time_in(steps) == pytest.approx(6e-6)
    assert t.op_time_in(t.module_runs("ingest"), pattern="pallas") == pytest.approx(0.8e-6)
    assert t.op_time("all-reduce")["0"] == pytest.approx(0.5e-6)
    gaps = sorted(t.idle_gaps(), key=lambda g: g[0])
    assert [round((b - a) * 1e9) for a, b in gaps] == [1000, 1200, 500]
    host = {"waiting_for_batch": [(50.0 + 3e-6, 50.0 + 4e-6)]}
    b = trace.breakdown(t, host)
    assert b["idle_gaps"][0][0] == "host_other" and b["idle_gaps"][0][1] == pytest.approx(1.2e-6)
    assert ["waiting_for_batch", pytest.approx(1e-6)] in b["idle_gaps"]
    assert b["device_ops"][0][0] == "fusion.1"


def test_recorded_chip_trace():
    """Two steps of rn18.s3 recorded on a TPU v5e; the expected numbers were
    read from the same reduction when the fixture was cut, and the step's
    device time matches the 54.4 ms the traced runs reported."""
    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    t = trace.DeviceTrace(rec["trace"], *rec["window"])
    want = rec["expected"]
    assert t.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    runs = t.module_runs(want["step_program"])
    assert len(runs) == want["step_runs"]
    assert t.op_time_in(runs) / len(runs) == pytest.approx(want["step_device_s"], rel=1e-9)
    assert 0 < t.busy_s() < t.window_s()
    assert 0.050 < want["step_device_s"] < 0.060
    assert t.module_runs("ingest") and t.op_time_in(t.module_runs("ingest"), pattern="custom|pallas|ingest") > 0


def test_mfu_of_the_recorded_step():
    """The step's analytic FLOPs over its device time in the recorded trace:
    2.79 TFLOP in 54.4 ms of a 197 TFLOP/s chip is about 26%."""
    from bench import harness, spec

    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    t = trace.DeviceTrace(rec["trace"], *rec["window"])
    cell = spec.resolve("rn18.s3", spec.load_benchmark())
    run = harness.Run(chips=1, images_per_step=256, window=t.window, step_ends=[], spans={},
                      stage_stats={}, config=cell.config, device=t,
                      peaks={"bf16_flops_per_s": 197e12}, family=cell.family)
    mfu = spec.reader("mfu")(run)
    assert 24 < mfu < 28
