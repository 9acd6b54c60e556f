"""Staged streaming pipeline (repro.core.pipeline) behaviour tests.

The determinism matrix is the load-bearing part: ``reorder="strict"`` must
reproduce the legacy loader's stream bit-for-bit (both impls, shuffle
on/off, drop_last on/off) and ``reorder="window"`` must yield a permutation
of it within each aligned window of batches.
"""
import numpy as np
import pytest

from repro.config import AutotuneConfig, LoaderConfig, PipelineConfig
from repro.core.loader import ConcurrentDataLoader
from repro.core.tracing import (
    STAGE_AUGMENT,
    STAGE_COLLATE,
    STAGE_DECODE,
    STAGE_FETCH,
    Tracer,
)
from repro.data.dataset import (
    ImageDataset,
    SpinDataset,
    SyntheticTokenDataset,
    TokenDataset,
)
from repro.data.imagenet_synth import SyntheticImageStore
from repro.data.store import InMemoryStore, SimulatedS3Store

N_ITEMS = 96
BS = 16


@pytest.fixture(scope="module")
def dataset():
    store = SyntheticImageStore(N_ITEMS, seed=0, avg_kb=4)
    sim = SimulatedS3Store(store, latency_mean_s=0.004, bandwidth_per_conn=1e9,
                           max_connections=64)
    return ImageDataset(sim, N_ITEMS, out_size=24)


def epoch(dataset, **kw):
    cfg = LoaderConfig(batch_size=BS, num_workers=2, prefetch_factor=2,
                       num_fetch_workers=8, seed=11, **kw)
    return list(ConcurrentDataLoader(dataset, cfg))


def digest(batches):
    return [(float(b["image"].sum()), b["label"].tolist()) for b in batches]


# -- determinism matrix ------------------------------------------------------


@pytest.mark.parametrize("impl", ["threaded", "asyncio"])
@pytest.mark.parametrize("shuffle", [True, False])
@pytest.mark.parametrize("drop_last", [True, False])
def test_strict_bit_identical_to_legacy(dataset, impl, shuffle, drop_last):
    kw = dict(impl=impl, shuffle=shuffle, drop_last=drop_last)
    ref = digest(epoch(dataset, pipeline=False, **kw))
    got = digest(epoch(dataset, pipeline=True, reorder="strict", **kw))
    assert got == ref


@pytest.mark.parametrize("shuffle", [True, False])
@pytest.mark.parametrize("drop_last", [True, False])
def test_window_is_permutation_within_each_window(dataset, shuffle, drop_last):
    W = 3
    kw = dict(impl="threaded", shuffle=shuffle, drop_last=drop_last)
    ref = epoch(dataset, pipeline=False, **kw)
    win = epoch(dataset, pipeline=True, reorder="window", reorder_window=W, **kw)
    assert len(win) == len(ref)
    # batch sizes line up slot for slot (matters for the drop_last=False tail)
    assert [len(b["label"]) for b in win] == [len(b["label"]) for b in ref]
    for g in range(0, len(ref), W):
        ref_labels = sorted(np.concatenate([b["label"] for b in ref[g:g + W]]).tolist())
        win_labels = sorted(np.concatenate([b["label"] for b in win[g:g + W]]).tolist())
        assert win_labels == ref_labels, f"window group {g // W} not a permutation"


def test_window_sample_content_identical(dataset):
    """Out-of-order assembly must not change any sample's *content* (the
    augmentation RNG is keyed by index, not batch position)."""
    ref = epoch(dataset, pipeline=False, impl="threaded")
    win = epoch(dataset, pipeline=True, reorder="window", reorder_window=2,
                impl="threaded")
    by_label_ref = {}
    for b in ref:
        for i, lbl in enumerate(b["label"].tolist()):
            by_label_ref.setdefault(lbl, []).append(b["image"][i])
    for b in win:
        for i, lbl in enumerate(b["label"].tolist()):
            # labels can repeat (synthetic store), and same-label samples may
            # legitimately swap order inside a window — match content against
            # ANY remaining ref sample of that label, then consume it
            cands = by_label_ref[lbl]
            match = next(
                (j for j, arr in enumerate(cands)
                 if (b["image"][i] == arr).all()),
                None,
            )
            assert match is not None, f"sample with label {lbl} has no ref twin"
            cands.pop(match)
    assert all(not v for v in by_label_ref.values())


# -- pipeline mechanics ------------------------------------------------------


def test_monolithic_fallback_for_unsplittable_dataset():
    ds = SyntheticTokenDataset(64, 16, 100)
    assert not ds.supports_split()
    ref = list(ConcurrentDataLoader(
        ds, LoaderConfig(batch_size=8, num_workers=2, shuffle=False)))
    got = list(ConcurrentDataLoader(
        ds, LoaderConfig(batch_size=8, num_workers=2, shuffle=False, pipeline=True)))
    assert all((a["tokens"] == b["tokens"]).all() for a, b in zip(ref, got))


def test_token_dataset_split_path_matches_getitem():
    from repro.data.dataset import build_token_store

    store = InMemoryStore()
    build_token_store(store, 8, 16, 100)
    ds = TokenDataset(store, 8, 16)
    assert ds.supports_split()
    whole = ds[3]
    split = ds.augment_item(ds.decode_raw(ds.get_raw(3), 3), 3)
    assert (whole["tokens"] == split["tokens"]).all()
    assert whole["nbytes"] == split["nbytes"]


def test_stage_spans_and_stats(dataset):
    tr = Tracer()
    cfg = LoaderConfig(batch_size=BS, num_workers=2, pipeline=True, seed=1)
    dl = ConcurrentDataLoader(dataset, cfg, tracer=tr)
    it = iter(dl)
    batches = list(it)
    n_batches, n_items = len(batches), sum(len(b["label"]) for b in batches)
    assert len(tr.spans(STAGE_FETCH)) == n_items
    assert len(tr.spans(STAGE_DECODE)) == n_items
    assert len(tr.spans(STAGE_AUGMENT)) == n_items
    assert len(tr.spans(STAGE_COLLATE)) == n_batches
    stats = dl.stage_stats()
    assert stats is not None
    assert stats["emitted_batches"] == n_batches
    assert stats["in_flight_samples"] == 0
    assert stats["decode_queue"]["depth"] >= 1
    # legacy mode exposes no stage stats
    dl2 = ConcurrentDataLoader(dataset, LoaderConfig(batch_size=BS, num_workers=2))
    list(dl2)
    assert dl2.stage_stats() is None


def test_pipeline_exception_propagates():
    class Bad(SyntheticTokenDataset):
        def __getitem__(self, i):
            if i == 13:
                raise ValueError("boom")
            return super().__getitem__(i)

    ds = Bad(64, 16, 100)
    cfg = LoaderConfig(batch_size=8, num_workers=2, shuffle=False, timeout_s=10,
                       pipeline=True)
    with pytest.raises(ValueError, match="boom"):
        list(ConcurrentDataLoader(ds, cfg))


def test_pipeline_transient_failures_retried():
    store = SyntheticImageStore(32, seed=0, avg_kb=2)
    sim = SimulatedS3Store(store, latency_mean_s=0.0, failure_rate=0.1, seed=2)
    ds = ImageDataset(sim, 32, out_size=16)
    cfg = LoaderConfig(batch_size=8, num_workers=2, timeout_s=30, pipeline=True)
    batches = list(ConcurrentDataLoader(ds, cfg))
    assert len(batches) == 4
    assert sim.stats.failures > 0


def test_pipeline_multi_epoch_and_resume(dataset):
    cfg = LoaderConfig(batch_size=BS, num_workers=2, seed=5, pipeline=True)
    dl = ConcurrentDataLoader(dataset, cfg)
    dl.set_epoch(0)
    e0 = [b["label"].tolist() for b in dl]
    dl.set_epoch(1)
    assert [b["label"].tolist() for b in dl] != e0
    dl.set_epoch(0)
    assert [b["label"].tolist() for b in dl] == e0

    # resume: same protocol as the legacy loader's test — a fresh loader
    # continues where the checkpointed consumer position left off
    dl = ConcurrentDataLoader(dataset, cfg)
    it = iter(dl)
    next(it), next(it)
    state = dl.state_dict()
    rest = [b["label"].tolist() for b in it]
    dl2 = ConcurrentDataLoader(dataset, cfg)
    dl2.load_state_dict(state)
    resumed = [b["label"].tolist() for b in dl2]
    assert resumed[: len(rest)] == rest


def test_window_checkpoint_rounds_down_to_group_boundary(dataset):
    """A windowed batch holds first-N-ready samples from its whole group, so
    the consumer cursor must only advance at group boundaries — a mid-group
    restart replays the partial group instead of dropping samples."""
    W = 2
    cfg = LoaderConfig(batch_size=BS, num_workers=2, seed=5, pipeline=True,
                       reorder="window", reorder_window=W)
    dl = ConcurrentDataLoader(dataset, cfg)
    it = iter(dl)
    first = next(it)
    assert dl.state_dict()["next_batch"] == 0  # mid-group: replay from 0
    second = next(it)
    assert dl.state_dict()["next_batch"] == W  # group 0 fully delivered
    state = dl.state_dict()
    for _ in it:
        pass
    # resume from the group boundary: delivered-before-checkpoint + resumed
    # together cover the epoch's full sample multiset (nothing lost)
    dl2 = ConcurrentDataLoader(dataset, cfg)
    dl2.load_state_dict(state)
    resumed = [b["label"].tolist() for b in dl2]
    got = sorted(first["label"].tolist() + second["label"].tolist()
                 + sum(resumed, []))
    full = sorted(sum((b["label"].tolist()
                       for b in ConcurrentDataLoader(dataset, cfg)), []))
    assert got == full


def test_sharded_pipeline_window_counts_batches(dataset):
    """Host-sharded batches hold batch_size/num_hosts samples; the prefetch
    window must still admit ``outstanding`` BATCHES, not num_hosts x more."""
    cfg = LoaderConfig(batch_size=BS, num_workers=2, prefetch_factor=2,
                       seed=3, pipeline=True)
    dl = ConcurrentDataLoader(dataset, cfg, host_id=0, num_hosts=2)
    it = iter(dl)
    assert it._dispatched_batches <= it.max_outstanding
    h0 = list(it)
    assert all(len(b["label"]) == BS // 2 for b in h0)
    # the two shards still partition the full batch exactly (legacy contract)
    h1 = list(ConcurrentDataLoader(dataset, cfg, host_id=1, num_hosts=2))
    full = list(ConcurrentDataLoader(dataset, cfg))
    for b0, b1, fb in zip(h0, h1, full):
        merged = np.concatenate([b0["label"], b1["label"]])
        assert (merged == fb["label"]).all()


def test_pipeline_autotune_knobs_move(dataset):
    at = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                        warmup_windows=0)
    cfg = LoaderConfig(batch_size=4, num_workers=1, prefetch_factor=2,
                       io_workers=2, cpu_workers=2, pipeline=True,
                       seed=5, autotune=at)
    dl = ConcurrentDataLoader(dataset, cfg)
    for ep in range(3):
        dl.set_epoch(ep)
        list(dl)
    probed = {e.knob for e in dl.autotuner.events if e.action == "probe"}
    assert probed & {"io_workers", "cpu_workers", "outstanding", "stage_queue"}
    # learned values persist across epochs on the loader
    assert dl._tuned


def test_pipeline_hedging_rescues_stragglers():
    from repro.data.store import ObjectStore

    class StragglerStore(ObjectStore):
        """~3% of keys stall 50x on their FIRST attempt only; the duplicate
        is fast — exactly the case hedging wins (mirrors the legacy test)."""

        def __init__(self, base):
            import threading
            self.base = base
            self._lock = threading.Lock()
            self._seen = {}

        def get(self, key):
            import time
            idx = int(key.split("/")[-1].split(".")[0])
            with self._lock:
                first = key not in self._seen
                self._seen[key] = True
            time.sleep(0.4 if (first and idx % 31 == 0) else 0.005)
            return self.base.get(key)

        def put(self, key, data):
            self.base.put(key, data)

        def list_keys(self, prefix=""):
            return self.base.list_keys(prefix)

    base = SyntheticImageStore(128, seed=0, avg_kb=2)
    ds = ImageDataset(StragglerStore(base), 128, out_size=16)
    cfg = LoaderConfig(impl="threaded", batch_size=32, num_workers=1,
                       num_fetch_workers=16, hedge_requests=True,
                       hedge_factor=3.0, hedge_min_s=0.05, pipeline=True)
    dl = ConcurrentDataLoader(ds, cfg)
    batches = list(dl)
    assert len(batches) == 4
    assert dl.hedge is not None and dl.hedge.hedges_issued > 0


def test_abandoned_iterator_threads_collected(dataset):
    """Dropping a mid-epoch iterator must free its stage threads even with
    autotune bound: knob callbacks hold the iterator only weakly, so
    refcount collection triggers __del__/shutdown."""
    import gc
    import threading
    import time

    at = AutotuneConfig(enabled=True)
    cfg = LoaderConfig(batch_size=BS, num_workers=2, pipeline=True, seed=1,
                       autotune=at)
    dl = ConcurrentDataLoader(dataset, cfg)
    it = iter(dl)
    next(it)
    before = threading.active_count()
    del it
    gc.collect()
    time.sleep(0.5)
    assert threading.active_count() < before, "stage threads leaked"
    # the dead callbacks are inert: a knob move reports the echo, no crash
    for k in dl.autotuner.knobs:
        k.set(k.get() or 1)


def test_bad_reorder_config_rejected(dataset):
    with pytest.raises(ValueError, match="reorder"):
        ConcurrentDataLoader(dataset, LoaderConfig(reorder="sorted"))
    with pytest.raises(ValueError, match="reorder_window"):
        ConcurrentDataLoader(
            dataset, LoaderConfig(pipeline=True, reorder_window=0))


# -- process CPU stage (the GIL escape) --------------------------------------


def spin_digest(ds, **kw):
    base = dict(batch_size=8, num_workers=2, prefetch_factor=2,
                seed=11, timeout_s=60)
    base.update(kw)
    return [(b["x"].tolist(), b["label"].tolist())
            for b in ConcurrentDataLoader(ds, LoaderConfig(**base))]


def test_process_cpu_stage_bit_identical_across_epochs():
    ds = SpinDataset(48, item_bytes=256, spin_rounds=2)
    cfg = LoaderConfig(batch_size=8, num_workers=2, seed=3, timeout_s=60)
    proc_cfg = LoaderConfig(batch_size=8, num_workers=2, seed=3, timeout_s=60,
                            pipeline=True, cpu_executor="process",
                            cpu_workers=2)
    ref_dl = ConcurrentDataLoader(ds, cfg)
    dl = ConcurrentDataLoader(ds, proc_cfg)
    for ep in range(2):  # epoch 2 exercises pool reuse + dataset rebind
        ref_dl.set_epoch(ep)
        dl.set_epoch(ep)
        ref = [(b["x"].tolist(), b["label"].tolist()) for b in ref_dl]
        got = [(b["x"].tolist(), b["label"].tolist()) for b in dl]
        assert got == ref, f"epoch {ep} diverged"
    stats = dl.stage_stats()
    assert stats["cpu_executor"] == "process"
    assert stats["cpu_pool"]["crashes"] == 0


def test_process_worker_crash_retries_sample_and_strict_order_survives():
    import os
    import signal
    import time

    ds = SpinDataset(96, item_bytes=2048, spin_rounds=20)
    cfg = LoaderConfig(batch_size=8, num_workers=2, seed=3, timeout_s=60,
                       pipeline=True, cpu_executor="process", cpu_workers=2)
    ref = [b["label"].tolist() for b in ConcurrentDataLoader(
        ds, LoaderConfig(batch_size=8, num_workers=2, seed=3, timeout_s=60))]
    dl = ConcurrentDataLoader(ds, cfg)
    it = iter(dl)
    got = [next(it)["label"].tolist()]
    # kill a worker that is BUSY (has a task in flight) mid-epoch
    deadline = time.monotonic() + 15
    killed = False
    while not killed and time.monotonic() < deadline:
        for w in list(it.cpu.pool.workers):
            if w.sids and w.proc.pid:
                os.kill(w.proc.pid, signal.SIGKILL)
                killed = True
                break
    assert killed, "no busy worker to kill — epoch finished too fast"
    got += [b["label"].tolist() for b in it]
    # the killed worker's sample was requeued onto a fresh worker: the
    # stream is complete and still bit-exactly ordered
    assert got == ref
    pool = dl.stage_stats()["cpu_pool"]
    assert pool["crashes"] >= 1
    assert pool["respawns"] >= 1
    assert pool["requeued"] >= 1


def test_process_executor_requires_picklable_dataset():
    class Unpicklable(SpinDataset):
        def __init__(self):
            super().__init__(16, item_bytes=64, spin_rounds=1)
            self._fn = lambda x: x  # lambdas don't pickle

    dl = ConcurrentDataLoader(
        Unpicklable(),
        LoaderConfig(batch_size=4, num_workers=1, pipeline=True,
                     cpu_executor="process"),
    )
    with pytest.raises(ValueError, match="picklable"):
        iter(dl)


def test_image_dataset_pickles_without_store():
    import pickle

    store = SyntheticImageStore(8, seed=0, avg_kb=2)
    ds = ImageDataset(store, 8, out_size=16, tracer=Tracer())
    clone = pickle.loads(pickle.dumps(ds))
    assert clone.store is None  # the CPU stages never touch it
    raw = ds.get_raw(3)
    a = ds.augment_item(ds.decode_raw(raw, 3), 3)
    b = clone.augment_item(clone.decode_raw(raw, 3), 3)
    assert (a["image"] == b["image"]).all()


def test_bad_cpu_executor_rejected(dataset):
    with pytest.raises(ValueError, match="cpu_executor"):
        ConcurrentDataLoader(dataset, LoaderConfig(cpu_executor="fork"))


# -- autotuned CPU stage -----------------------------------------------------


@pytest.mark.parametrize("hedge", [False, True])
def test_autotuned_process_stage_binds_independent_knobs_and_resizes(hedge):
    """One CPU stage, chosen at construction: the controller tunes its width
    with the independent knobs, and a mid-epoch resize keeps the strict
    stream bit-identical to autotune off."""
    from repro.core.pipeline import PROC_PREFILL_DEPTH, _ProcCPUStage

    ds = SpinDataset(64, item_bytes=256, spin_rounds=2)
    # a long interval keeps the controller's own probes out of the test:
    # every knob move below is the test's
    at = AutotuneConfig(enabled=True, interval_batches=10_000,
                        tune_hedge=hedge)
    pipe = PipelineConfig(enabled=True, cpu_workers=1, cpu_executor="process",
                          transport="shm", slab_slots=8)
    cfg = LoaderConfig(batch_size=8, num_workers=1, prefetch_factor=2, seed=9,
                       timeout_s=60, pipeline=pipe, autotune=at,
                       hedge_requests=hedge)
    dl = ConcurrentDataLoader(ds, cfg)
    it = iter(dl)
    assert isinstance(it.cpu, _ProcCPUStage)
    knobs = {k.name: k for k in dl.autotuner.knobs}
    assert set(knobs) == (
        {"io_workers", "cpu_workers", "outstanding", "stage_queue",
         "slab_slots"} | ({"hedge"} if hedge else set())
    )
    batches = [next(it)]
    assert knobs["cpu_workers"].set(3) == 3
    assert it.cpu.width == 3
    assert it.cpu.gate.limit == PROC_PREFILL_DEPTH * 3
    assert len(it.cpu.pool.workers) == 3
    batches.append(next(it))
    assert knobs["cpu_workers"].set(2) == 2
    assert it.stage_stats()["cpu_workers"] == 2
    batches += list(it)
    got = [(b["x"].tolist(), b["label"].tolist()) for b in batches]
    assert got == spin_digest(ds, batch_size=8, num_workers=1,
                              prefetch_factor=2, seed=9)
    assert dl.stage_stats()["cpu_executor"] == "process"
    assert dl._tuned["cpu_workers"] == 2


def test_autotuned_unsplittable_dataset_binds_no_cpu_knobs():
    """A monolithic dataset sends nothing through the CPU stage or its
    queue, so their knobs would only waste the controller's probes."""
    ds = SyntheticTokenDataset(64, 16, 100)
    at = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                        warmup_windows=0)
    cfg = LoaderConfig(batch_size=4, num_workers=1, prefetch_factor=2,
                       shuffle=False, pipeline=True, seed=5, timeout_s=60,
                       autotune=at)
    dl = ConcurrentDataLoader(ds, cfg)
    it = iter(dl)
    assert not it.split
    knob_names = {k.name for k in dl.autotuner.knobs}
    assert knob_names == {"io_workers", "outstanding"}
    got = list(it)
    ref = list(ConcurrentDataLoader(
        ds, LoaderConfig(batch_size=4, num_workers=1, shuffle=False)))
    assert len(got) == len(ref)
    assert all((a["tokens"] == b["tokens"]).all() for a, b in zip(ref, got))


class _RefusesPickle(SpinDataset):
    """A split dataset that counts and refuses every pickling attempt."""

    attempts = 0

    def __getstate__(self):
        type(self).attempts += 1
        raise TypeError("this dataset does not pickle")


@pytest.mark.parametrize("executor", ["thread", "process"])
def test_only_the_process_stage_pickles_the_dataset(executor):
    ds = _RefusesPickle(32, item_bytes=256, spin_rounds=1)
    _RefusesPickle.attempts = 0
    kw = dict(batch_size=8, num_workers=1, prefetch_factor=2, seed=4)
    dl = ConcurrentDataLoader(ds, LoaderConfig(
        timeout_s=60, pipeline=PipelineConfig(enabled=True,
                                              cpu_executor=executor),
        **kw))
    if executor == "process":
        with pytest.raises(ValueError, match="picklable"):
            iter(dl)
        assert _RefusesPickle.attempts == 1
        return
    it = iter(dl)
    assert it.cpu_kind == "thread" and it._proc_payload is None
    got = [(b["x"].tolist(), b["label"].tolist()) for b in it]
    assert got == spin_digest(SpinDataset(32, item_bytes=256, spin_rounds=1),
                              **kw)
    assert _RefusesPickle.attempts == 0
