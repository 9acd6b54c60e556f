"""Autotuner tests: controller convergence on synthetic profiles, knob-bound
safety, mid-epoch fetcher resize determinism, and autotune=off equivalence."""
import time

import pytest

from repro.config import AutotuneConfig, LoaderConfig
from repro.core.autotune import AutotuneController, Knob
from repro.core.fetcher import (
    AdjustableSemaphore,
    AsyncioFetcher,
    HedgeTracker,
    ThreadPoolFetcher,
)
from repro.core.loader import ConcurrentDataLoader
from repro.core.tracing import Tracer, window_summary
from repro.data.dataset import ImageDataset
from repro.data.imagenet_synth import SyntheticImageStore
from repro.data.store import SimulatedS3Store

N_ITEMS = 96
BS = 16


@pytest.fixture(scope="module")
def dataset():
    store = SyntheticImageStore(N_ITEMS, seed=0, avg_kb=4)
    sim = SimulatedS3Store(store, latency_mean_s=0.004, bandwidth_per_conn=1e9,
                           max_connections=64)
    return ImageDataset(sim, N_ITEMS, out_size=24)


def digest(batches):
    return [(float(b["image"].sum()), b["label"].tolist()) for b in batches]


# ---------------------------------------------------------------------------
# controller on synthetic throughput profiles (no threads, no sleeping)
# ---------------------------------------------------------------------------


def drive(ctrl, vals, tput_fn, steps):
    """Feed the controller a deterministic clock: each batch takes
    1/tput(current knobs) seconds."""
    now = 0.0
    for _ in range(steps):
        now += 1.0 / tput_fn(vals)
        ctrl.on_batch(1, now=now)
    return now


def synthetic_knobs(vals, bounds):
    def mk(name):
        lo, hi = bounds[name]

        def setter(v, name=name, lo=lo, hi=hi):
            vals[name] = max(lo, min(int(v), hi))
            return vals[name]

        return Knob(name, lambda name=name: vals[name], setter, lo, hi)

    return [mk(n) for n in vals]


def test_controller_converges_on_synthetic_profile():
    # tput rises with both knobs, plateaus at fetch>=16, out>=8
    def tput(v):
        return min(v["fetch"], 16) * min(v["out"], 8)

    vals = {"fetch": 1, "out": 1}
    bounds = {"fetch": (1, 64), "out": (1, 64)}
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         warmup_windows=1, rel_improvement=0.05)
    ctrl = AutotuneController(cfg, synthetic_knobs(vals, bounds))
    drive(ctrl, vals, tput, steps=300)
    best = 16 * 8
    assert tput(vals) >= 0.8 * best, (vals, ctrl.events)
    assert any(e.action == "accept" for e in ctrl.events)


def test_controller_goes_quiescent_on_flat_profile():
    vals = {"fetch": 4, "out": 4}
    bounds = {"fetch": (1, 64), "out": (1, 64)}
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         patience=2, reprobe_windows=0)  # heartbeat off
    ctrl = AutotuneController(cfg, synthetic_knobs(vals, bounds))
    drive(ctrl, vals, lambda v: 100.0, steps=200)
    assert any(e.action == "quiesce" for e in ctrl.events)
    # heartbeat disabled: once quiescent on a stable profile, no probing
    events = list(ctrl.events)
    last_quiesce = max(i for i, e in enumerate(events)
                       if e.action == "quiesce")
    assert all(e.action in ("quiesce", "restore")
               for e in events[last_quiesce:])


def test_reprobe_heartbeat_escapes_premature_park():
    """Two early noise-reverts can park the controller at a bad point whose
    throughput is stable (no collapse to trigger a re-arm); the heartbeat
    must re-probe and resume climbing."""
    state = {"lie": True}  # first probes measure a fake regression

    def tput(v):
        if state["lie"]:
            return 10.0 if v["fetch"] > 1 else 20.0  # punishes the climb
        return min(v["fetch"], 16) * 20.0

    vals = {"fetch": 1, "out": 4}
    bounds = {"fetch": (1, 64), "out": (4, 4)}  # single movable knob
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         patience=1, reprobe_windows=4)
    ctrl = AutotuneController(cfg, synthetic_knobs(vals, bounds))
    drive(ctrl, vals, tput, steps=12)
    assert any(e.action == "quiesce" for e in ctrl.events)  # parked at fetch=1
    assert vals["fetch"] == 1
    state["lie"] = False  # the true profile rewards concurrency
    drive(ctrl, vals, tput, steps=80)
    assert any(e.action == "reprobe" for e in ctrl.events)
    assert vals["fetch"] >= 16, (vals, ctrl.events)


def test_controller_rearms_on_regime_change():
    state = {"collapse": False}

    def tput(v):
        base = min(v["fetch"], 16) * 10.0
        return base * (0.05 if state["collapse"] else 1.0)

    vals = {"fetch": 16, "out": 4}
    bounds = {"fetch": (1, 64), "out": (1, 64)}
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         patience=1)
    ctrl = AutotuneController(cfg, synthetic_knobs(vals, bounds))
    drive(ctrl, vals, tput, steps=60)
    assert any(e.action == "quiesce" for e in ctrl.events)
    state["collapse"] = True  # storage got 20x slower
    drive(ctrl, vals, tput, steps=60)
    assert any(e.action == "rearm" for e in ctrl.events)


def test_controller_never_exceeds_bounds():
    # adversarial deterministic "noise": tput jumps around wildly, provoking
    # accepts/reverts in all directions
    def tput(v):
        return 1.0 + ((v["fetch"] * 7919 + v["out"] * 104729) % 97)

    seen = []
    vals = {"fetch": 4, "out": 4}
    lo, hi = 2, 32

    def setter(name):
        def s(v):
            seen.append(v)
            vals[name] = max(lo, min(int(v), hi))
            return vals[name]

        return s

    knobs = [Knob(n, lambda n=n: vals[n], setter(n), lo, hi)
             for n in ("fetch", "out")]
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         patience=1000)  # never quiesce
    ctrl = AutotuneController(cfg, knobs)
    drive(ctrl, vals, tput, steps=500)
    assert seen, "controller never probed"
    assert all(lo <= v <= hi for v in seen), sorted(set(seen))


def test_binary_knob_reverts_unconvincing_flip():
    flips = []
    vals = {"hedge": 0}

    def setter(v):
        flips.append(v)
        vals["hedge"] = int(v)
        return vals["hedge"]

    knob = Knob("hedge", lambda: vals["hedge"], setter, 0, 1)
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         patience=2)
    ctrl = AutotuneController(cfg, [knob])
    drive(ctrl, vals, lambda v: 50.0, steps=50)  # flat: flips never help
    assert vals["hedge"] == 0  # always rolled back
    assert any(e.action == "revert" and e.knob == "hedge" for e in ctrl.events)


def test_step_schedule_coarse_then_fine():
    """The first probe jumps by the coarse factor; after a hold/revert on the
    knob the next probe uses the finer factor."""
    vals = {"fetch": 1}
    bounds = {"fetch": (1, 256)}
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         patience=1000)  # default schedule: (4, 2)
    ctrl = AutotuneController(cfg, synthetic_knobs(vals, bounds))
    drive(ctrl, vals, lambda v: 100.0, steps=40)  # flat: every probe holds
    probes = [e.value for e in ctrl.events if e.action == "probe"]
    assert probes[0] == 4  # coarse x4 from 1
    assert probes[1] == 8  # refined to x2 after the hold
    assert all(b == 2 * a for a, b in zip(probes[1:], probes[2:]))  # stays fine


def test_knob_step_schedule_override():
    vals = {"fetch": 1}
    knob = synthetic_knobs(vals, {"fetch": (1, 256)})[0]
    knob.step_schedule = (8, 2)
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         patience=1000)
    ctrl = AutotuneController(cfg, [knob])
    drive(ctrl, vals, lambda v: 100.0, steps=20)
    probes = [e.value for e in ctrl.events if e.action == "probe"]
    assert probes[0] == 8 and probes[1] == 16


def test_additive_knob_steps_by_one():
    vals = {"policy": 0}

    def setter(v):
        vals["policy"] = max(0, min(int(v), 2))
        return vals["policy"]

    knob = Knob("policy", lambda: vals["policy"], setter, 0, 2,
                scale="add", step_schedule=(1,))
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         patience=2, reprobe_windows=0)
    ctrl = AutotuneController(cfg, [knob])
    # policy 1 is strictly best: the controller must land and stay there
    drive(ctrl, vals, lambda v: (50.0, 200.0, 10.0)[v["policy"]], steps=120)
    assert vals["policy"] == 1, ctrl.events
    probed = {e.value for e in ctrl.events if e.action == "probe"}
    assert probed <= {0, 1, 2}


def test_util_gate_blocks_up_probes_until_headroom():
    """A saturated training step (busy fraction >= util_gate) must stop the
    controller from buying more loader throughput; headroom re-enables it."""
    busy = {"frac": 0.98}
    vals = {"fetch": 4}
    bounds = {"fetch": (1, 64)}
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         util_gate=0.9, patience=1000)
    ctrl = AutotuneController(cfg, synthetic_knobs(vals, bounds),
                              util_fn=lambda: busy["frac"])
    drive(ctrl, vals, lambda v: min(v["fetch"], 32) * 10.0, steps=40)
    assert vals["fetch"] == 4  # nothing bought while the accelerator is full
    assert not any(e.action == "probe" for e in ctrl.events)
    assert any(e.action == "gate" for e in ctrl.events)
    assert not any(e.action == "quiesce" for e in ctrl.events)  # stayed armed
    busy["frac"] = 0.3  # headroom appeared (e.g. a bigger model step ended)
    drive(ctrl, vals, lambda v: min(v["fetch"], 32) * 10.0, steps=120)
    assert vals["fetch"] >= 32, (vals, ctrl.events)


def test_util_gate_off_when_no_signal():
    vals = {"fetch": 4}
    bounds = {"fetch": (1, 64)}
    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         util_gate=0.9, patience=1000)
    ctrl = AutotuneController(cfg, synthetic_knobs(vals, bounds),
                              util_fn=lambda: None)  # no step spans yet
    drive(ctrl, vals, lambda v: min(v["fetch"], 32) * 10.0, steps=60)
    assert any(e.action == "probe" for e in ctrl.events)
    assert vals["fetch"] > 4


def test_trainer_ring_wires_util_signal(dataset):
    """_make_ring must hand the controller a utilization signal exactly when
    a real tracer is present (NULL_TRACER has no step spans to read)."""
    from repro.core.tracing import NULL_TRACER
    from repro.train.trainer import _make_ring

    at = AutotuneConfig(enabled=True)
    cfg = LoaderConfig(impl="threaded", batch_size=BS, num_workers=2,
                       prefetch_factor=2, num_fetch_workers=4, seed=5,
                       autotune=at)
    dl = ConcurrentDataLoader(dataset, cfg)
    ring = _make_ring(dl, depth=2, tracer=NULL_TRACER)
    assert dl.autotuner.util_fn is None
    ring.close()
    tracer = Tracer()
    ring = _make_ring(dl, depth=2, tracer=tracer)
    assert dl.autotuner.util_fn is not None
    assert dl.autotuner.util_fn() is None  # no step spans yet -> no signal
    now = time.monotonic()
    tracer.record("run_training_batch", now - 0.5, now)
    assert dl.autotuner.util_fn() > 0.0
    ring.close()


def test_tracer_recent_spans_bounded_scan():
    tr = Tracer()
    t = 1000.0
    for i in range(50):
        tr.record("step", t + i, t + i + 0.5)
    tr.record("other", t + 49, t + 49.5)
    recent = tr.recent_spans("step", since=t + 48.0)
    assert [s.t0 for s in recent] == [t + 48, t + 49]  # oldest first
    assert tr.recent_spans("step", since=t + 100.0) == []
    # slightly out-of-order completion near the window edge is still found
    tr.record("step", t + 48.2, t + 48.4)
    assert len(tr.recent_spans("step", since=t + 48.0)) == 3


def test_recent_busy_fraction_windowing():
    from repro.core.tracing import RUN_TRAINING_BATCH
    from repro.core.utilization import recent_busy_fraction

    tr = Tracer()
    now = time.monotonic()
    assert recent_busy_fraction(tr, window_s=1.0, now=now) is None
    # half the window (anchored at the last completed span) covered
    tr.record(RUN_TRAINING_BATCH, now - 0.5, now)
    assert abs(recent_busy_fraction(tr, window_s=1.0, now=now) - 0.5) < 1e-6
    # spans overlapping the window edge are clipped, not dropped
    tr.record(RUN_TRAINING_BATCH, now - 2.0, now - 0.9)
    f = recent_busy_fraction(tr, window_s=1.0, now=now)
    assert abs(f - 0.6) < 1e-6
    # long-step regime: queried MID-step (1 s into an unrecorded in-flight
    # step), the window anchors at the last completed step and reads the
    # true saturation instead of counting the in-flight time as idle
    tr2 = Tracer()
    tr2.record(RUN_TRAINING_BATCH, now - 4.0, now - 2.0)
    tr2.record(RUN_TRAINING_BATCH, now - 2.0, now)
    assert recent_busy_fraction(tr2, window_s=1.0, now=now + 1.0) == 1.0
    # ...but a stale anchor (paused training / very long step) is no signal
    assert recent_busy_fraction(tr2, window_s=1.0, now=now + 3.0) is None


# ---------------------------------------------------------------------------
# resizable fetchers / adjustable primitives
# ---------------------------------------------------------------------------


def test_adjustable_semaphore_resize():
    sem = AdjustableSemaphore(2)
    assert sem.acquire(timeout=0.1) and sem.acquire(timeout=0.1)
    assert not sem.acquire(timeout=0.05)  # at limit
    sem.set_limit(3)
    assert sem.acquire(timeout=0.1)  # raised limit admits immediately
    sem.set_limit(1)  # shrink below held count: drains, never interrupts
    sem.release()
    sem.release()
    assert not sem.acquire(timeout=0.05)  # still 1 held >= limit 1
    sem.release()
    assert sem.acquire(timeout=0.1)
    with pytest.raises(ValueError):
        sem.set_limit(0)


def test_threadpool_fetcher_resize_clamps(dataset):
    f = ThreadPoolFetcher(4, hard_cap=16)
    try:
        assert f.concurrency == 4
        assert f.resize(8) == 8
        assert f.resize(99) == 16  # clamped to hard cap
        assert f.resize(0) == 1
        items = f.fetch(dataset, list(range(8)))
        assert len(items) == 8
    finally:
        f.close()


def test_asyncio_fetcher_resize(dataset):
    f = AsyncioFetcher(4, hard_cap=16)
    try:
        assert f.resize(12) == 12
        assert f.resize(64) == 16
        items = f.fetch(dataset, list(range(6)))
        assert len(items) == 6
    finally:
        f.close()


def test_hedge_tracker_enable_toggle(dataset):
    hedge = HedgeTracker(factor=3.0, min_s=0.05)
    hedge.enabled = False
    f = ThreadPoolFetcher(4, hedge=hedge)
    try:
        f.fetch(dataset, list(range(4)))
        assert hedge.hedges_issued == 0  # disabled tracker: no hedging path
    finally:
        f.close()


def test_window_summary_aggregates():
    tr = Tracer()
    t = time.monotonic()
    for i in range(10):
        tr.record("stage_a", t + i * 0.01, t + i * 0.01 + 0.005)
    tr.record("stage_b", t, t + 1.0)
    w = window_summary(tr, ["stage_a", "stage_b", "stage_c"], t - 1.0,
                       t + 10.0)
    assert w["stage_a"].count == 10
    assert abs(w["stage_a"].mean_s - 0.005) < 1e-9
    assert w["stage_b"].count == 1
    assert w["stage_c"].count == 0 and w["stage_c"].rate_per_s == 0.0
    # spans ending outside the window are excluded
    w2 = window_summary(tr, ["stage_a"], t + 0.02, t + 0.04)
    assert w2["stage_a"].count < 10


# ---------------------------------------------------------------------------
# loader integration: determinism under live resizing, off == stock
# ---------------------------------------------------------------------------


def _stream(dataset, **cfg_kw):
    cfg = LoaderConfig(impl="threaded", batch_size=BS, num_workers=2,
                       prefetch_factor=2, num_fetch_workers=8, seed=11,
                       **cfg_kw)
    dl = ConcurrentDataLoader(dataset, cfg)
    return digest(list(dl))


def test_autotune_off_is_stock_behavior(dataset):
    stock = _stream(dataset)
    off = _stream(dataset, autotune=AutotuneConfig(enabled=False))
    assert stock == off
    cfg = LoaderConfig(impl="threaded", batch_size=BS,
                       autotune=AutotuneConfig(enabled=False))
    dl = ConcurrentDataLoader(dataset, cfg)
    assert dl.autotuner is None  # no controller object, no hook in __next__


@pytest.mark.parametrize("impl", ["threaded", "asyncio"])
def test_autotune_on_preserves_stream(dataset, impl):
    cfg_kw = dict(impl=impl, batch_size=BS, num_workers=2, prefetch_factor=2,
                  num_fetch_workers=8, seed=11)
    stock = digest(list(ConcurrentDataLoader(dataset, LoaderConfig(**cfg_kw))))
    at = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                        max_fetch_workers=16, max_outstanding=16)
    tuned = digest(list(ConcurrentDataLoader(
        dataset, LoaderConfig(autotune=at, **cfg_kw))))
    assert stock == tuned


def test_midepoch_resize_preserves_batch_order(dataset):
    """Resizing every worker's fetch pool between batches must not change the
    delivered stream (the reorder buffer owns ordering)."""
    cfg = LoaderConfig(impl="threaded", batch_size=BS, num_workers=2,
                       prefetch_factor=2, num_fetch_workers=8, seed=11)
    ref = digest(list(ConcurrentDataLoader(dataset, cfg)))

    dl = ConcurrentDataLoader(dataset, cfg)
    it = iter(dl)
    out = []
    sizes = [1, 16, 2, 8, 4]
    for i, batch in enumerate(it):
        out.append(batch)
        for w in it.workers:
            w.fetcher.resize(sizes[i % len(sizes)])
    assert digest(out) == ref


def test_autotune_state_persists_across_epochs(dataset):
    at = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                        max_fetch_workers=16, max_outstanding=16)
    cfg = LoaderConfig(impl="threaded", batch_size=BS, num_workers=2,
                       prefetch_factor=2, num_fetch_workers=2, seed=11,
                       autotune=at)
    dl = ConcurrentDataLoader(dataset, cfg)
    list(dl)
    tuned_after_e0 = dict(dl._tuned)
    dl.set_epoch(1)
    it = iter(dl)
    next(it)
    # the new iterator starts from the learned values, not cfg defaults
    if "fetch_workers" in tuned_after_e0:
        assert it._fetch_workers == dl._tuned["fetch_workers"]
    it.shutdown()


def test_attach_ring_knob_bounds():
    class FakeRing:
        def __init__(self):
            self.depth = 2
            self.max_depth = 6

        def set_depth(self, d):
            self.depth = max(1, min(int(d), self.max_depth))
            return self.depth

    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         min_device_prefetch=1, max_device_prefetch=8)
    ctrl = AutotuneController(cfg, [])
    ring = FakeRing()
    ctrl.attach_ring(ring)
    (knob,) = ctrl.knobs
    assert knob.name == "device_prefetch"
    assert (knob.lo, knob.hi) == (1, 6)  # capped by the ring's own max_depth
    assert knob.set(99) == 6
    assert ring.depth == 6


def test_reattach_known_knob_keeps_quiescence():
    """A converged controller must stay parked when the next epoch re-attaches
    a knob it already learned (e.g. the per-epoch DevicePrefetchRing)."""
    vals = {"depth": 2}

    def setter(v):
        vals["depth"] = max(1, min(int(v), 8))
        return vals["depth"]

    def mk():
        return Knob("depth", lambda: vals["depth"], setter, 1, 8)

    cfg = AutotuneConfig(enabled=True, interval_batches=1, min_window_s=0.0,
                         patience=1, reprobe_windows=0)
    ctrl = AutotuneController(cfg, [])
    ctrl.attach_knob(mk())
    drive(ctrl, vals, lambda v: min(v["depth"], 4) * 25.0, steps=60)
    assert any(e.action == "quiesce" for e in ctrl.events)
    tuned = vals["depth"]
    n_events = len(ctrl.events)
    ctrl.attach_knob(mk())  # next epoch: same control surface, new object
    assert vals["depth"] == tuned  # learned value re-applied
    drive(ctrl, vals, lambda v: min(v["depth"], 4) * 25.0, steps=30)
    probes_after = [e for e in list(ctrl.events)[n_events:]
                    if e.action == "probe"]
    assert not probes_after  # still quiescent — no probing restarted


def test_autotune_never_caps_static_config(dataset):
    """Turning the tuner ON with bounds below the explicit static config must
    widen the bounds, not silently clamp the loader below its off baseline."""
    at = AutotuneConfig(enabled=True, max_outstanding=4, max_fetch_workers=4)
    cfg = LoaderConfig(impl="threaded", batch_size=BS, num_workers=2,
                       prefetch_factor=8, num_fetch_workers=8, autotune=at)
    it = iter(ConcurrentDataLoader(dataset, cfg))
    assert it.max_outstanding == 16  # num_workers * prefetch_factor, uncapped
    assert it._fetch_workers == 8
    it.shutdown()


@pytest.mark.parametrize("with_reorder", [False, True])
def test_build_pipeline_knobs_slab_bound_and_reorder(with_reorder):
    from repro.core.autotune import build_pipeline_knobs

    cfg = AutotuneConfig(enabled=True, max_slab_slots=512)
    state = {"io": 8, "cpu": 2, "out": 4, "q": 64, "slab": 8, "win": 2}

    def setter(key):
        def s(n):
            state[key] = int(n)
            return int(n)
        return s

    extra = (dict(get_reorder=lambda: state["win"], set_reorder=setter("win"))
             if with_reorder else {})
    knobs = build_pipeline_knobs(
        cfg,
        get_io=lambda: state["io"], set_io=setter("io"),
        get_cpu=lambda: state["cpu"], set_cpu=setter("cpu"),
        get_outstanding=lambda: state["out"], set_outstanding=setter("out"),
        get_queue=lambda: state["q"], set_queue=setter("q"),
        get_slab=lambda: state["slab"], set_slab=setter("slab"), max_slab=8,
        **extra,
    )
    by_name = {k.name: k for k in knobs}
    assert set(by_name) == (
        {"io_workers", "cpu_workers", "outstanding", "stage_queue",
         "slab_slots"} | ({"reorder_window"} if with_reorder else set())
    )
    # the slab knob never reaches past the slots the pool allocated
    slab = by_name["slab_slots"]
    assert (slab.lo, slab.hi) == (cfg.min_slab_slots, 8)


def test_weak_knob_callbacks_release_their_owner():
    """Once the owner dies, get reports 0 and set echoes its argument."""
    from repro.core.autotune import make_weak_knob_callbacks

    class Owner:
        value = 5

    owner = Owner()
    wget, wset = make_weak_knob_callbacks(owner)
    g = wget(lambda it: it.value)
    s = wset(lambda it, n: n + it.value)
    assert g() == 5 and s(2) == 7
    del owner
    import gc

    gc.collect()
    assert g() == 0 and s(2) == 2


# ---------------------------------------------------------------------------
# cooperative AIMD down-shedding (CongestionBoard-wired controller)
# ---------------------------------------------------------------------------


def _drive_on(ctrl, vals, tput_fn, steps, now):
    """Like ``drive`` but continues an existing clock — multi-phase shed
    tests must not rewind time between phases."""
    for _ in range(steps):
        now += 1.0 / tput_fn(vals)
        ctrl.on_batch(1, now=now)
    return now


def _shed_cfg(**kw):
    base = dict(enabled=True, interval_batches=1, min_window_s=0.0,
                warmup_windows=1, rel_improvement=0.05,
                shed_collapse_fraction=0.5, shed_md_factor=0.5,
                shed_hold_windows=1, shed_recover_windows=4,
                shed_min_interval_s=0.0)
    base.update(kw)
    return AutotuneConfig(**base)


def _plateau(v):
    return min(v["fetch"], 16) * 4


def test_shed_cuts_multiplicatively_and_recovers_additively(tmp_path):
    from repro.core.coord import CongestionBoard

    vals = {"fetch": 1}
    ctrl = AutotuneController(
        _shed_cfg(), synthetic_knobs(vals, {"fetch": (1, 64)}),
        congestion=CongestionBoard(str(tmp_path), host="a"),
    )
    collapsed = {"on": False}

    def tput(v):
        return 0.1 if collapsed["on"] else _plateau(v)

    now = drive(ctrl, vals, tput, steps=200)
    pre = vals["fetch"]
    assert pre >= 16  # converged before the collapse
    collapsed["on"] = True
    now = _drive_on(ctrl, vals, tput, 2, now)
    assert any(e.action == "shed" for e in ctrl.events)
    assert vals["fetch"] == max(1, pre // 2)  # multiplicative decrease
    # collapse clears: additive climb back to the pre-shed operating point
    collapsed["on"] = False
    _drive_on(ctrl, vals, tput, 12, now)
    recovers = [e for e in ctrl.events if e.action == "recover"]
    assert len(recovers) >= 2  # several additive steps, not one jump
    assert vals["fetch"] >= pre
    # the shed landed on the fleet board
    board = CongestionBoard(str(tmp_path), host="x")
    assert board.last_seq() >= 1


def test_peer_shed_event_cuts_this_host(tmp_path):
    from repro.core.coord import CongestionBoard

    vals = {"fetch": 1}
    ctrl = AutotuneController(
        _shed_cfg(), synthetic_knobs(vals, {"fetch": (1, 64)}),
        congestion=CongestionBoard(str(tmp_path), host="b"),
    )
    now = drive(ctrl, vals, _plateau, steps=200)
    pre = vals["fetch"]
    # another host observes the collapse first and posts fleet-wide
    CongestionBoard(str(tmp_path), host="a").post_shed(1.0)
    _drive_on(ctrl, vals, _plateau, 2, now)
    assert any(e.action == "shed_peer" for e in ctrl.events)
    assert vals["fetch"] == max(1, pre // 2)
    # we honored the peer's event without stacking our own on the board
    assert not any(e.action == "shed" for e in ctrl.events)


def test_shed_off_without_congestion_board():
    vals = {"fetch": 1}
    collapsed = {"on": False}

    def tput(v):
        return 0.1 if collapsed["on"] else _plateau(v)

    ctrl = AutotuneController(_shed_cfg(),
                              synthetic_knobs(vals, {"fetch": (1, 64)}))
    now = drive(ctrl, vals, tput, steps=200)
    collapsed["on"] = True
    _drive_on(ctrl, vals, tput, 5, now)
    assert not any(e.action in ("shed", "shed_peer") for e in ctrl.events)


def test_shed_leaves_binary_knobs_alone(tmp_path):
    from repro.core.coord import CongestionBoard

    vals = {"fetch": 8, "hedge": 1}
    # (0, 1) bounds make "hedge" a binary toggle (Knob.is_binary)
    knobs = synthetic_knobs(vals, {"fetch": (1, 64), "hedge": (0, 1)})
    ctrl = AutotuneController(
        _shed_cfg(), knobs,
        congestion=CongestionBoard(str(tmp_path), host="a"),
    )
    now = drive(ctrl, vals, _plateau, steps=60)
    CongestionBoard(str(tmp_path), host="peer").post_shed(1.0)
    fetch_pre, hedge_pre = vals["fetch"], vals["hedge"]
    _drive_on(ctrl, vals, _plateau, 2, now)
    assert vals["fetch"] < fetch_pre  # scalable knob cut...
    assert vals["hedge"] == hedge_pre  # ...binary toggle untouched
