"""The reader of ``io_width``, on hand-built runs."""
from bench import spec
from bench.harness import Run


def _run(stage_stats):
    return Run(chips=1, images_per_step=256, window=(10.0, 20.0), step_ends=[20.0],
               spans={}, stage_stats=stage_stats, config={})


def test_io_width_reads_the_limit_at_the_end():
    stats = {"io_workers": 247, "io_width": {"seed": 64, "limit": 247, "peak": 309,
                                             "widened": 8, "narrowed": 1}}
    assert spec.reader("io_width")(_run(stats)) == 247


def test_io_width_reads_none_without_the_counter():
    # a program whose IO width is fixed (or that predates the counter)
    assert spec.reader("io_width")(_run({"io_workers": 64})) is None
    assert spec.reader("io_width")(_run({})) is None
