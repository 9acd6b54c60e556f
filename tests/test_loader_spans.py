"""The loader's and trainer's own spans: the trainer's wait on the device
ring (``loader_wait``), IO admission (``io_admit``) and the IO hand-off
(``io_handoff``); their copies on the profiler's clock; and the program
names by which a profiler trace's readers find the step and the ingest."""
import glob
import importlib.util
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LoaderConfig, ModelConfig, PipelineConfig, TrainConfig
from repro.core.loader import ConcurrentDataLoader
from repro.core.tracing import (
    BATCH_TO_DEVICE,
    IO_ADMIT,
    IO_HANDOFF,
    LOADER_WAIT,
    NULL_TRACER,
    RUN_TRAINING_BATCH,
    STAGE_FETCH,
    Tracer,
)
from repro.data.dataset import ImageDataset
from repro.data.imagenet_synth import SyntheticImageStore
from repro.data.store import SimulatedS3Store
from repro.kernels.ingest_norm.ops import make_ingest_fn
from repro.train.steps import init_resnet_train_state, make_resnet_train_step
from repro.train.trainer import Trainer, raw_train_loop

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ITEMS = 48
BS = 8
LANES = (LOADER_WAIT, RUN_TRAINING_BATCH, BATCH_TO_DEVICE)


def _bench_module(*parts):
    """A module of the chip benchmark (``bench/``), loaded by its path."""
    path = os.path.join(ROOT, "bench", *parts)
    spec = importlib.util.spec_from_file_location("bench_" + "_".join(parts)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def dataset():
    store = SyntheticImageStore(N_ITEMS, seed=0, avg_kb=4)
    sim = SimulatedS3Store(store, latency_mean_s=0.002, bandwidth_per_conn=1e9,
                           max_connections=64)
    return ImageDataset(sim, N_ITEMS, out_size=16)


def _loader(dataset, tracer, impl="threaded"):
    cfg = LoaderConfig(batch_size=BS, num_workers=2, num_fetch_workers=4, impl=impl,
                       pipeline=PipelineConfig(enabled=True), seed=3)
    return ConcurrentDataLoader(dataset, cfg, tracer=tracer)


def _step(state, batch):
    return state + 1, {"loss": jnp.mean(batch["image"])}


@pytest.mark.parametrize("impl", ["threaded", "asyncio"])
def test_loader_spans_per_step_and_sample(dataset, impl):
    tr = Tracer()
    res = Trainer(_step, jnp.zeros(()), tracer=tr, donate=False).fit(_loader(dataset, tr, impl))
    assert res.steps == N_ITEMS // BS

    waits = [s.args["step"] for s in tr.spans(LOADER_WAIT)]
    # one wait per step, and the last next(ring) waited for the epoch's end
    assert waits == list(range(res.steps + 1))
    assert len(tr.spans(RUN_TRAINING_BATCH)) == res.steps

    def ids(name):
        return sorted((s.args["index"], s.args["batch_id"]) for s in tr.spans(name))

    fetched = ids(STAGE_FETCH)
    assert len(fetched) == N_ITEMS and len(set(fetched)) == N_ITEMS
    assert ids(IO_ADMIT) == fetched
    assert ids(IO_HANDOFF) == fetched
    # per sample: admitted before its GET starts; the hand-off starts where
    # the GET ends
    fetch = {s.args["index"]: s for s in tr.spans(STAGE_FETCH)}
    for s in tr.spans(IO_ADMIT):
        assert s.t0 <= s.t1 <= fetch[s.args["index"]].t0
    for s in tr.spans(IO_HANDOFF):
        assert s.t0 == fetch[s.args["index"]].t1 <= s.t1


class _CountingAnnotation:
    entered = 0

    def __init__(self, name, **kwargs):
        self.name = name

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("loop", ["fit", "raw"])
def test_null_tracer_records_and_annotates_nothing(dataset, monkeypatch, loop):
    monkeypatch.setattr(_CountingAnnotation, "entered", 0)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    if loop == "fit":
        res = Trainer(_step, jnp.zeros(()), donate=False).fit(_loader(dataset, NULL_TRACER))
    else:
        res = raw_train_loop(_step, jnp.zeros(()), _loader(dataset, NULL_TRACER))
    assert res.steps == N_ITEMS // BS
    assert NULL_TRACER.spans() == []
    assert _CountingAnnotation.entered == 0

    # a live tracer enters one annotation per span of the three lanes
    tr = Tracer()
    raw_train_loop(_step, jnp.zeros(()), _loader(dataset, tr), tracer=tr)
    assert _CountingAnnotation.entered == sum(len(tr.spans(n)) for n in LANES)
    assert len(tr.spans(BATCH_TO_DEVICE)) == res.steps


def _annotations(logdir):
    """Host-plane events of a profiler trace: name -> [(start_ns, dur_ns)]."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append((e.start_ns, e.duration_ns))
    return out


def test_trainer_lanes_on_the_profiler_clock(dataset, tmp_path):
    """A trainer-lane span, mapped through two markers as the chip benchmark
    maps its trace (``bench/trace.py``), lands within 1 ms of its
    ``TraceAnnotation`` copy."""
    trace = _bench_module("trace.py")
    tr = Tracer()
    marks = {}

    def mark(name):
        marks[name] = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            pass

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        mark("bench:window_start")
        raw_train_loop(_step, jnp.zeros(()), _loader(dataset, tr), tracer=tr)
        time.sleep(0.05)
        mark("bench:window_end")
    finally:
        jax.profiler.stop_trace()
    events = _annotations(str(tmp_path))
    data = {"devices": {},
            "marks": [[n, events[n][0][0], events[n][0][1]] for n in marks]}
    to_mono = trace.DeviceTrace(data, marks["bench:window_start"],
                                marks["bench:window_end"]).to_mono
    for lane in LANES:
        spans = sorted(tr.spans(lane), key=lambda s: s.t0)
        copies = sorted(events.get(lane, []))
        assert spans and len(copies) == len(spans), lane
        for s, (start, dur) in zip(spans, copies):
            assert abs(to_mono(start) - s.t0) < 1e-3, lane
            assert abs(to_mono(start + dur) - s.t1) < 1e-3, lane


def _program_name(jitted, *args):
    text = jitted.lower(*args).compile().as_text()
    return re.match(r"HloModule (\S+?),", text).group(1)


def test_step_and_ingest_program_names_match_the_trace_readers():
    """The readers of the step's and the ingest's device time find them by
    program name; a renamed function must fail here, not turn a metric null."""
    step_program = _bench_module("metrics", "step_device_ms.py").STEP_PROGRAM
    ingest_program = _bench_module("metrics", "ingest_roofline.py").INGEST_PROGRAM
    mcfg = ModelConfig(name="tiny", family="resnet", resnet_blocks=(1, 1), resnet_width=8,
                       num_classes=10, image_size=16)
    tcfg = TrainConfig()
    state = init_resnet_train_state(mcfg, tcfg, jax.random.PRNGKey(0))
    trainer = Trainer(make_resnet_train_step(mcfg, tcfg), state)
    batch = {"image": jnp.zeros((2, 3, 16, 16), jnp.float32),
             "label": jnp.zeros((2,), jnp.int32)}
    assert re.search(step_program, _program_name(trainer.train_step, state, batch))
    step_hlo = trainer.train_step.lower(state, batch).as_text(debug_info=True)
    assert "/train_step/" in step_hlo

    ingest = make_ingest_fn()
    raw = {"image": jnp.asarray(np.zeros((2, 16, 16, 3), np.uint8)),
           "label": jnp.zeros((2,), jnp.int32)}
    assert re.search(ingest_program, _program_name(ingest, raw))
    assert "/ingest/" in ingest.lower(raw).as_text(debug_info=True)
