"""The derived IO width (``PipelineConfig.io_workers == 0``, no autotuner):
the IO gate widens while storage latency sets the pace, stays at its seed
where it does not, stops where the store's connection pool does, and leaves
an explicit width and the autotuner's gate alone."""
import multiprocessing
import os
import threading
import time

import pytest

from repro.config import AutotuneConfig, LoaderConfig, PipelineConfig
from repro.core.loader import ConcurrentDataLoader
from repro.core.pipeline import IO_LATENCY_TOL
from repro.core.tracing import IO_NARROWED, IO_WIDENED, Tracer
from repro.data.dataset import ImageDataset
from repro.data.imagenet_synth import SyntheticImageStore, item_key
from repro.data.store import InMemoryStore, SimulatedS3Store

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ITEMS = 384
BS = 16
SEED = 4  # num_workers 2 x num_fetch_workers 2
CEILING = 2 * 4 * BS  # num_workers x prefetch_factor batches outstanding
LATENCY_S = 0.03
CONNS = 6


@pytest.fixture(scope="module")
def memory_store():
    """Small image records held in memory: a GET costs microseconds."""
    synth = SyntheticImageStore(N_ITEMS, seed=0, avg_kb=1)
    store = InMemoryStore()
    for i in range(N_ITEMS):
        store.put(item_key(i), synth.get(item_key(i)))
    return store


def _s3(base, conns=256, sigma=0.0):
    return SimulatedS3Store(base, latency_mean_s=LATENCY_S, latency_sigma=sigma,
                            bandwidth_per_conn=1e9, max_connections=conns)


def _loader(store, io_workers=0, cpu_executor="thread", **kw):
    pipe = PipelineConfig(enabled=True, io_workers=io_workers, cpu_workers=2,
                          cpu_executor=cpu_executor)
    cfg = LoaderConfig(batch_size=BS, num_workers=2, num_fetch_workers=2,
                       prefetch_factor=4, seed=1, pipeline=pipe, **kw)
    ds = ImageDataset(store, N_ITEMS, out_size=8, augment=False)
    return ConcurrentDataLoader(ds, cfg, tracer=Tracer())


def _epoch(loader):
    """Drain one epoch; the IO gate's limit at each batch, and the seconds."""
    it = iter(loader)
    limits, t0 = [], time.monotonic()
    for _ in it:
        limits.append(it.io.gate.limit)
    return limits, time.monotonic() - t0


@pytest.mark.parametrize("store", ["s3", "memory", "s3_pool"])
def test_derived_width_follows_what_the_io_stage_observes(memory_store, store):
    if store == "memory":
        loader = _loader(memory_store)
    else:
        loader = _loader(_s3(memory_store, conns=CONNS if store == "s3_pool" else 256))
    limits, seconds = _epoch(loader)
    width = loader.stage_stats()["io_width"]
    assert width["seed"] == SEED
    assert width["limit"] == limits[-1]
    assert width["peak"] >= max(limits)
    assert min(limits) >= SEED
    counters = loader.tracer.counters()
    assert counters.get(IO_WIDENED, 0) == width["widened"]
    assert counters.get(IO_NARROWED, 0) == width["narrowed"]
    if store == "s3":
        # latency sets the pace: the gate widens, and the epoch beats the
        # same epoch at the seed's width pinned
        assert width["widened"] > 0 and width["peak"] > SEED
        _, pinned = _epoch(_loader(_s3(memory_store), io_workers=SEED))
        assert seconds < pinned
    elif store == "memory":
        # the CPU stage sets the pace: nothing to gain from more GETs
        assert width["widened"] == 0 and set(limits) == {SEED}
    else:
        # a pool of CONNS connections: the width stops within the latency
        # tolerance above it, far below the outstanding window
        assert width["widened"] > 0
        assert width["peak"] <= IO_LATENCY_TOL * CONNS < CEILING


@pytest.mark.parametrize("owner", ["explicit", "autotune"])
def test_io_width_left_to_its_owner(memory_store, owner):
    if owner == "explicit":
        loader = _loader(_s3(memory_store), io_workers=6)
    else:
        at = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                            warmup_windows=0)
        loader = _loader(_s3(memory_store), autotune=at)
    limits, _ = _epoch(loader)
    stats = loader.stage_stats()
    assert "io_width" not in stats
    counters = loader.tracer.counters()
    assert IO_WIDENED not in counters and IO_NARROWED not in counters
    if owner == "explicit":
        assert set(limits) == {6} and stats["io_workers"] == 6


def _digest(batches):
    return [(float(b["image"].sum()), b["label"].tolist()) for b in batches]


def test_strict_stream_bit_identical_while_widening(memory_store):
    store = _s3(memory_store, sigma=0.5)
    ref = _digest(ConcurrentDataLoader(
        ImageDataset(store, N_ITEMS, out_size=8, augment=False),
        LoaderConfig(batch_size=BS, num_workers=2, num_fetch_workers=2,
                     prefetch_factor=4, seed=1)))
    loader = _loader(store)
    assert _digest(loader) == ref
    assert loader.stage_stats()["io_width"]["widened"] > 0


def _io_threads():
    return [t for t in threading.enumerate() if t.name.startswith("pipe-io")]


@pytest.mark.parametrize("stop", ["iterator", "harness"])
def test_shutdown_joins_the_widened_io_threads(memory_store, monkeypatch, stop):
    loader = _loader(_s3(memory_store),
                     cpu_executor="process" if stop == "harness" else "thread")
    it = iter(loader)
    for _ in range(N_ITEMS // BS - 4):
        next(it)
        if len(_io_threads()) > SEED + 2:
            break
    assert it.io.gate.limit > SEED and len(_io_threads()) > SEED + 2
    if stop == "iterator":
        it.shutdown()
    else:
        monkeypatch.syspath_prepend(ROOT)
        from bench.harness import shutdown_loader

        shutdown_loader(loader)
        assert multiprocessing.active_children() == []
    assert _io_threads() == []
    assert not [t for t in threading.enumerate() if t.name.startswith("pipe-")]
    del it
