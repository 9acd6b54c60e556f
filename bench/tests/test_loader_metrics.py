"""The readers of the loader's spans: ``loader_wait_pct``, ``io_slots_held``
and ``io_admit_ms``, on hand-built runs."""
import pytest

from bench import spec
from bench.harness import Run


def _run(spans, window=(10.0, 20.0)):
    return Run(chips=1, images_per_step=256, window=window, step_ends=[window[1]],
               spans=spans, stage_stats={}, config={})


def read(name, spans):
    return spec.reader(name)(_run(spans))


def test_loader_wait_pct_clips_to_the_window_and_merges_overlaps():
    spans = {"loader_wait": [(8.0, 11.0, {"step": 0}),   # 1 s inside
                             (12.0, 14.0, {"step": 1}),
                             (13.0, 15.0, {"step": 2}),  # overlaps: union 12-15
                             (19.0, 22.0, {"step": 3}),  # 1 s inside
                             (25.0, 26.0, {"step": 4})]}  # outside
    assert read("loader_wait_pct", spans) == pytest.approx(100.0 * 5.0 / 10.0)


def test_io_slots_held_sums_fetch_and_handoff_without_hedges():
    spans = {
        "stage_fetch": [(9.0, 12.0, {"index": 0}),                 # 2 s inside
                        (12.0, 16.0, {"index": 1}),                # 4 s
                        (11.0, 15.0, {"index": 1, "hedge": True}),  # no permit
                        (19.5, 21.0, {"index": 2})],               # 0.5 s
        "io_handoff": [(12.0, 13.0, {"index": 0}),                 # 1 s
                       (15.0, 17.0, {"index": 1, "hedge": True}),  # no permit
                       (21.0, 22.0, {"index": 2})],                # outside
    }
    assert read("io_slots_held", spans) == pytest.approx((2.0 + 4.0 + 0.5 + 1.0) / 10.0)


def test_io_admit_ms_means_the_samples_admitted_in_the_window():
    spans = {"io_admit": [(5.0, 10.5, {"index": 0}),   # admitted inside: 5.5 s
                          (11.0, 11.5, {"index": 1}),  # 0.5 s
                          (19.0, 21.0, {"index": 2}),  # admitted after the window
                          (1.0, 9.0, {"index": 3})]}   # admitted before it
    assert read("io_admit_ms", spans) == pytest.approx(1e3 * (5.5 + 0.5) / 2)


@pytest.mark.parametrize("name", ["loader_wait_pct", "io_slots_held", "io_admit_ms"])
def test_no_spans_read_none(name):
    # no spans at all, and a program that records the older lanes but none
    # of the loader's own (as before they existed)
    older = {"stage_fetch": [(11.0, 12.0, {"index": 0})],
             "run_training_batch": [(11.0, 12.0, {"step": 0})]}
    assert read(name, {}) is None
    assert read(name, older) is None
