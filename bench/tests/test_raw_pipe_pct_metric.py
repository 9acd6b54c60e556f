"""The reader of ``raw_pipe_pct``, on hand-built runs."""
import pytest

from bench import spec
from bench.harness import Run


def _run(stage_stats):
    return Run(chips=1, images_per_step=256, window=(10.0, 20.0), step_ends=[20.0],
               spans={}, stage_stats=stage_stats, config={})


def _transport(**counts):
    return {"transport": dict({"kind": "shm", "shm_samples": 900, "pipe_samples": 100,
                               "fallbacks": {"no_slot": 100}}, **counts)}


@pytest.mark.parametrize("shm, pipe, pct", [(990, 10, 1.0), (0, 250, 100.0), (4096, 0, 0.0)])
def test_raw_pipe_pct_reads_the_share_sent_through_the_pipe(shm, pipe, pct):
    stats = _transport(raw_shm_samples=shm, raw_pipe_samples=pipe)
    assert spec.reader("raw_pipe_pct")(_run(stats)) == pytest.approx(pct)


def test_raw_pipe_pct_reads_none_without_the_counters():
    # a program that sends raw bytes only through the pipe, and counts nothing
    assert spec.reader("raw_pipe_pct")(_run(_transport())) is None
    # no process stage, or no task sent yet
    assert spec.reader("raw_pipe_pct")(_run({})) is None
    stats = _transport(raw_shm_samples=0, raw_pipe_samples=0)
    assert spec.reader("raw_pipe_pct")(_run(stats)) is None
