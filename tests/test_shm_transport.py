"""Zero-copy fast path tests: shm transport, pinned staging, device epilogue.

Three layers:

* unit tests over :mod:`repro.core.shm` (slot packing, generation guards,
  fallback reasons, the live cap) and :mod:`repro.core.staging` (pooled
  collate, release/GC recycling) — no processes involved;
* the end-to-end bit-identity matrix ``transport={pipe,shm}`` against the
  thread-stage reference, plus crash injection, oversized-sample fallback,
  and resume-cursor equivalence over the real process pool;
* a 4-device subprocess leg proving ``transport="shm"`` composes with
  sharded delivery (same pattern as test_delivery.py).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.config import LoaderConfig, PipelineConfig
from repro.core import shm as shm_mod
from repro.core.loader import ConcurrentDataLoader
from repro.core.staging import HostBatchPool
from repro.core.tracing import BYTES_COPIED, Tracer
from repro.data.dataset import ImageDataset, collate
from repro.data.imagenet_synth import SyntheticImageStore
from repro.data.store import SimulatedS3Store

N_ITEMS = 64
BS = 8


@pytest.fixture(scope="module")
def dataset():
    store = SyntheticImageStore(N_ITEMS, seed=0, avg_kb=4)
    sim = SimulatedS3Store(store, latency_mean_s=0.002, bandwidth_per_conn=1e9,
                           max_connections=64)
    return ImageDataset(sim, N_ITEMS, out_size=24)


def pipe_cfg(transport="pipe", executor="process", staging=0, slot_bytes=1 << 20,
             slots=8, **loader_kw):
    return LoaderConfig(
        batch_size=BS, num_workers=2, prefetch_factor=2, num_fetch_workers=8,
        seed=11, timeout_s=60,
        pipeline=PipelineConfig(
            enabled=True, cpu_workers=2, cpu_executor=executor,
            transport=transport, slab_slot_bytes=slot_bytes, slab_slots=slots,
            staging_buffers=staging,
        ),
        **loader_kw,
    )


def digest(batches):
    return [(float(b["image"].sum()), b["label"].tolist()) for b in batches]


def epoch(dataset, cfg, tracer=None):
    dl = ConcurrentDataLoader(dataset, cfg, tracer=tracer or Tracer())
    out = list(dl)
    stats = dl.stage_stats()
    pool = getattr(dl, "_cpu_pool", None)
    if pool is not None:
        pool.close()
    return out, stats


# --------------------------------------------------------------------------
# unit: slab writer / parent slab
# --------------------------------------------------------------------------


class TestSlab:
    def _pair(self, slot_bytes=4096, slots=4):
        parent = shm_mod.ParentSlab(slot_bytes, slots)
        writer = shm_mod.SlabWriter(*parent.spec())
        return parent, writer

    def test_pack_view_roundtrip(self):
        parent, writer = self._pair()
        try:
            item = {
                "image": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
                "label": np.int32(7),
                "nbytes": np.int64(123),
            }
            handle, why = writer.try_pack(item)
            assert why is None
            view = parent.view_item(handle)
            for k in item:
                np.testing.assert_array_equal(np.asarray(view[k]),
                                              np.asarray(item[k]))
            assert handle[2] == shm_mod.item_nbytes(item)
            view.release()
            writer.free_slots(parent.drain_freed())
            assert len(writer.free) == writer.slots
        finally:
            writer.close()
            parent.close()

    def test_detach_copies_out_and_frees_the_slot(self):
        parent, writer = self._pair()
        try:
            item = {"image": np.arange(12, dtype=np.float32), "label": np.int32(3)}
            handle, _ = writer.try_pack(item)
            view = parent.view_item(handle)
            assert view.slab_in_use() == 1
            out = view.detach()
            assert type(out) is dict and parent.in_use == 0
            assert parent.drain_freed() == [(handle[0], handle[1])]
            # the copy owns its memory: rewriting the slot leaves it intact
            writer.free_slots([(handle[0], handle[1])])
            writer.try_pack({"image": np.zeros(12, np.float32),
                             "label": np.int32(0)})
            for k in item:
                np.testing.assert_array_equal(out[k], item[k])
            view.release()  # already released: a no-op
            assert parent.in_use == 0
        finally:
            writer.close()
            parent.close()

    def test_stale_generation_free_ignored(self):
        parent, writer = self._pair()
        try:
            handle, _ = writer.try_pack({"x": np.zeros(4)})
            slot, gen = handle[0], handle[1]
            writer.free_slots([(slot, gen)])
            before = len(writer.free)
            # double-free with the now-stale generation: must not re-free
            writer.free_slots([(slot, gen)])
            assert len(writer.free) == before
            assert writer.gens[slot] == gen + 1
        finally:
            writer.close()
            parent.close()

    def test_fallback_reasons(self):
        parent, writer = self._pair(slot_bytes=256, slots=2)
        try:
            _, why = writer.try_pack({"x": np.zeros(1024, dtype=np.uint8)})
            assert why == shm_mod.FALLBACK_OVERSIZE
            _, why = writer.try_pack({"x": np.array([object()], dtype=object)})
            assert why == shm_mod.FALLBACK_RAGGED
            h1, _ = writer.try_pack({"x": np.zeros(8)})
            h2, _ = writer.try_pack({"x": np.zeros(8)})
            assert h1 is not None and h2 is not None
            _, why = writer.try_pack({"x": np.zeros(8)})
            assert why == shm_mod.FALLBACK_NO_SLOT
        finally:
            writer.close()
            parent.close()

    def test_live_cap_skims_high_slots(self):
        parent, writer = self._pair(slots=4)
        try:
            writer.set_cap(1)
            h, _ = writer.try_pack({"x": np.zeros(4)})
            assert h[0] == 0  # only slot 0 usable
            _, why = writer.try_pack({"x": np.zeros(4)})
            assert why == shm_mod.FALLBACK_NO_SLOT
            writer.set_cap(4)  # slots 1-3 are still in the deque, usable again
            h2, _ = writer.try_pack({"x": np.zeros(4)})
            assert h2 is not None
        finally:
            writer.close()
            parent.close()

    def test_reset_reclaims_everything_and_stales_old_handles(self):
        parent, writer = self._pair()
        try:
            handle, _ = writer.try_pack({"x": np.zeros(4)})
            writer.reset()
            assert len(writer.free) == writer.slots
            before = len(writer.free)
            writer.free_slots([(handle[0], handle[1])])  # pre-reset gen
            assert len(writer.free) == before
        finally:
            writer.close()
            parent.close()

    def test_shm_item_release_idempotent(self):
        parent, writer = self._pair()
        try:
            handle, _ = writer.try_pack({"x": np.arange(4)})
            item = parent.view_item(handle)
            item.release()
            item.release()
            assert parent.drain_freed() == [(handle[0], handle[1])]
            assert parent.drain_freed() == []
        finally:
            writer.close()
            parent.close()


# --------------------------------------------------------------------------
# unit: pinned staging pool
# --------------------------------------------------------------------------


class TestStaging:
    def test_collate_matches_default_and_reuses(self):
        pool = HostBatchPool(depth=2)
        items = [{"image": np.full((3, 4), i, np.float32), "label": np.int32(i)}
                 for i in range(4)]
        ref = collate(items)
        got = pool.collate(items)
        for k in ref:
            np.testing.assert_array_equal(got[k], ref[k])
            assert got[k].ctypes.data % 4096 == 0  # page-aligned lease
        got.release()
        again = pool.collate(items)
        assert pool.stats()["reuses"] == 1
        again.release()

    def test_release_idempotent_and_pool_bounded(self):
        pool = HostBatchPool(depth=1)
        items = [{"x": np.zeros(8, np.float32)}]
        a = pool.collate(items)
        b = pool.collate(items)  # beyond depth: ephemeral
        a.release()
        a.release()
        b.release()
        s = pool.stats()
        assert s["allocs"] == 1 and s["ephemeral"] == 1


# --------------------------------------------------------------------------
# end-to-end: bit-identity matrix + fallbacks + crash + resume
# --------------------------------------------------------------------------


def test_transport_matrix_bit_identical(dataset):
    ref, _ = epoch(dataset, pipe_cfg(executor="thread"))
    want = digest(ref)
    for transport, staging in (("pipe", 0), ("shm", 0), ("shm", 2)):
        got, stats = epoch(dataset, pipe_cfg(transport=transport,
                                             staging=staging))
        assert digest(got) == want, f"{transport}/staging={staging} diverged"
        t = stats["transport"]
        assert t["kind"] == transport
        if transport == "shm":
            assert t["shm_samples"] > 0
            assert t["slab_slots"] == 8
        if staging:
            assert stats["staging"]["leases"] >= len(got)


def test_shm_halves_transport_copies(dataset):
    tr_pipe, tr_shm = Tracer(), Tracer()
    a, _ = epoch(dataset, pipe_cfg("pipe"), tracer=tr_pipe)
    b, stats = epoch(dataset, pipe_cfg("shm"), tracer=tr_shm)
    assert digest(a) == digest(b)
    # pipe pays serialize+deserialize (2x) per sample, shm one slab write;
    # both then pay the same collate copy
    assert stats["transport"]["fallback_rate"] < 0.5
    assert tr_shm.counter(BYTES_COPIED) < tr_pipe.counter(BYTES_COPIED)


def test_oversized_samples_fall_back_to_pipe(dataset):
    ref, _ = epoch(dataset, pipe_cfg("pipe"))
    # slots far smaller than one decoded image: every sample takes the
    # pickle fallback, stream still bit-identical
    got, stats = epoch(dataset, pipe_cfg("shm", slot_bytes=512, slots=2))
    assert digest(got) == digest(ref)
    t = stats["transport"]
    assert t["shm_samples"] == 0
    assert t["fallbacks"].get("oversize", 0) > 0


def test_crash_mid_slab_write_retries_and_stream_survives(dataset):
    ref, _ = epoch(dataset, pipe_cfg("pipe"))
    dl = ConcurrentDataLoader(dataset, pipe_cfg("shm"))
    it = iter(dl)
    got = [next(it)["label"].tolist()]
    # worker 0 poisons its next slot write and dies without sending the
    # handle; the parent must retire the slab, respawn, and retry the sample
    it.cpu.pool.inject_crash(mode="mid_slab_write", worker=0)
    got += [b["label"].tolist() for b in it]
    assert got == [d[1] for d in digest(ref)]
    stats = dl.stage_stats()
    assert stats["cpu_pool"]["crashes"] >= 1
    assert stats["cpu_pool"]["respawns"] >= 1
    pool = getattr(dl, "_cpu_pool", None)
    if pool is not None:
        pool.close()


class _NoDatasetInWorkers(ImageDataset):
    """Pickles in the parent, refuses to unpickle in a spawned worker."""

    def __setstate__(self, state):
        import multiprocessing

        if multiprocessing.parent_process() is not None:
            raise RuntimeError("dataset refused in a worker")
        super().__setstate__(state)


def test_workers_dying_at_start_fail_the_epoch_with_their_diagnostic(dataset):
    ds = _NoDatasetInWorkers(dataset.store, N_ITEMS, out_size=24)
    dl = ConcurrentDataLoader(ds, pipe_cfg("shm"))
    try:
        # no worker ever starts, so no sample is sent to one: the pump must
        # fail the epoch itself instead of respawning until the timeout
        with pytest.raises(RuntimeError, match="died while starting.*refused"):
            list(dl)
        assert dl.stage_stats()["cpu_pool"]["crashes"] >= 3
    finally:
        pool = getattr(dl, "_cpu_pool", None)
        if pool is not None:
            pool.close()


def test_resume_cursor_equivalence_across_transports(dataset):
    unbroken, _ = epoch(dataset, pipe_cfg("shm"))
    dl = ConcurrentDataLoader(dataset, pipe_cfg("shm"))
    it = iter(dl)
    head = [digest([next(it)])[0] for _ in range(2)]
    state = dl.state_dict()
    it.shutdown()
    pool = getattr(dl, "_cpu_pool", None)
    if pool is not None:
        pool.close()
    # resume on the OTHER transport: the cursor is transport-agnostic
    dl2 = ConcurrentDataLoader(dataset, pipe_cfg("pipe"))
    dl2.load_state_dict(state)
    rest = digest(list(dl2))
    assert head + rest == digest(unbroken)
    pool = getattr(dl2, "_cpu_pool", None)
    if pool is not None:
        pool.close()


def test_transport_validation():
    with pytest.raises(ValueError, match="transport"):
        ConcurrentDataLoader(
            None, LoaderConfig(pipeline=PipelineConfig(enabled=True,
                                                       transport="rdma")))
    with pytest.raises(ValueError, match="slab"):
        ConcurrentDataLoader(
            None, LoaderConfig(pipeline=PipelineConfig(
                enabled=True, transport="shm", slab_slots=0)))
    with pytest.raises(ValueError, match="staging_buffers"):
        ConcurrentDataLoader(
            None, LoaderConfig(pipeline=PipelineConfig(enabled=True,
                                                       staging_buffers=-1)))


# --------------------------------------------------------------------------
# device epilogue: uint8 host batches + fused on-device normalize
# --------------------------------------------------------------------------


def test_device_epilogue_matches_host_epilogue(dataset):
    import jax.numpy as jnp

    from repro.kernels.ingest_norm.ops import make_ingest_fn

    store = dataset.store
    u8 = ImageDataset(store, N_ITEMS, out_size=24, epilogue="device")
    host_batches, _ = epoch(dataset, pipe_cfg("shm"))
    u8_batches, _ = epoch(u8, pipe_cfg("shm"))
    assert u8_batches[0]["image"].dtype == np.uint8
    fn = make_ingest_fn()  # ref impl on CPU; ImageNet mean/std
    for hb, ub in zip(host_batches, u8_batches):
        out = fn({k: jnp.asarray(v) for k, v in ub.items()})
        np.testing.assert_allclose(np.asarray(out["image"]), hb["image"],
                                   rtol=2e-6, atol=2e-6)
        np.testing.assert_array_equal(np.asarray(out["label"]), hb["label"])

    with pytest.raises(ValueError, match="epilogue"):
        ImageDataset(store, N_ITEMS, epilogue="gpu")


def test_ring_applies_ingest_and_releases_staged_batches(dataset):
    from repro.core.prefetch import DevicePrefetchRing
    from repro.kernels.ingest_norm.ops import make_ingest_fn

    u8 = ImageDataset(dataset.store, N_ITEMS, out_size=24, epilogue="device")
    dl = ConcurrentDataLoader(u8, pipe_cfg("shm", staging=2))
    ring = DevicePrefetchRing(iter(dl), depth=2, ingest_fn=make_ingest_fn())
    batches = list(ring)
    ring.close()
    assert len(batches) == N_ITEMS // BS
    for b in batches:
        assert b["image"].dtype == np.float32  # normalized on device
        assert b["image"].shape == (BS, 3, 24, 24)
    stats = dl.stage_stats()
    # every staged lease came back: the ring released after each transfer
    st = stats.get("staging")
    assert st is not None and st["leases"] >= len(batches)
    pool = getattr(dl, "_cpu_pool", None)
    if pool is not None:
        pool.close()


@pytest.mark.parametrize("rows", [3, 0])
def test_ring_transfers_high_rank_arrays_exactly(rows):
    """Rank > 2 leaves cross as rows and take their shape back on device."""
    from repro.core.prefetch import DevicePrefetchRing

    rng = np.random.default_rng(rows)
    batch = {"image": rng.integers(0, 255, (rows, 5, 4, 3), dtype=np.uint8),
             "mask": rng.random((rows, 2, 3)).astype(np.float32),
             "tokens": rng.integers(0, 9, (rows, 7)).astype(np.int32),
             "label": np.arange(rows, dtype=np.int32)}
    ring = DevicePrefetchRing(iter([batch]), depth=1)
    (got,) = list(ring)
    ring.close()
    for k, v in batch.items():
        assert got[k].shape == v.shape and got[k].dtype == v.dtype
        np.testing.assert_array_equal(np.asarray(got[k]), v)


# --------------------------------------------------------------------------
# sharded delivery × shm transport (4-device subprocess)
# --------------------------------------------------------------------------

SHARDED_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
from repro.config import DeliverySpec, LoaderConfig, PipelineConfig
from repro.core import make_loader
from repro.data.dataset import ImageDataset
from repro.data.imagenet_synth import SyntheticImageStore
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("data",))

def loader(transport, delivery):
    return make_loader(
        LoaderConfig(batch_size=16, seed=3,
                     pipeline=PipelineConfig(enabled=True, io_workers=8,
                                             cpu_workers=2,
                                             cpu_executor="process",
                                             transport=transport,
                                             slab_slots=8,
                                             staging_buffers=2),
                     delivery=delivery),
        ImageDataset(SyntheticImageStore(48, seed=0, avg_kb=4), 48,
                     out_size=32, augment=False),
    )

rec = {}
host = list(loader("pipe", DeliverySpec.host()))
shm_sharded_loader = loader("shm", DeliverySpec.sharded(mesh))
shm_sharded = list(shm_sharded_loader)
rec["gather_equal"] = len(host) == len(shm_sharded) and all(
    np.array_equal(np.asarray(jax.device_get(sb[k])), hb[k])
    for hb, sb in zip(host, shm_sharded) for k in hb
)
rec["device_resident"] = all(
    isinstance(b["image"], jax.Array) and len(b["image"].sharding.device_set) == 4
    for b in shm_sharded
)
stats = shm_sharded_loader.stage_stats()
rec["transport_kind"] = stats["transport"]["kind"]
rec["shm_samples"] = stats["transport"]["shm_samples"]
rec["lane_staging"] = [p["leases"] for p in stats["delivery"]["staging"]]
print(json.dumps(rec))
'''


def test_shm_transport_with_sharded_delivery_4dev():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT], capture_output=True, text=True,
        env=env, timeout=600, cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["gather_equal"], rec
    assert rec["device_resident"], rec
    assert rec["transport_kind"] == "shm"
    assert rec["shm_samples"] > 0
    assert all(n > 0 for n in rec["lane_staging"]), rec
