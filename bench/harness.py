"""One run of one cell: set-up, a measured window, then the check.

The window drives the program's training path as ``launch/train.py`` wires
it: ``make_loader`` over the dataset of the cell's family, whose store is
the benchmark's (``bench/storage.py``), and ``Trainer.fit`` with its device
prefetch ring on the family's jitted step. What depends on the family (the
object pool, the dataset, the state, the step, a device epilogue, the
references and the numbers compared) comes from
``bench/families/<family>.py``; what every family shares is here. It drives
one chip; a cell on several chips needs the sharded delivery path added
here first.

Set-up ends when the warm-up steps are done; the first of them compile, and
the first three are the ones the check follows. The window then runs for
``--seconds`` and closes at the first step that completes after that; a
callback on the trainer ends ``fit`` there by raising ``WindowClosed``.
With ``--trace 1`` the program records its spans and a profiler trace covers
the window; without it the program gets ``NULL_TRACER`` and no profiler
runs. The profiler records the device and the benchmark's markers, and
neither Python calls nor the runtime's host activity: under jax's default
options, which trace every Python call of every thread, a traced window
completed a fifth of an untraced one's steps.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import multiprocessing
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from bench import check, spec, storage

CHECKED = check.CHECKED_STEPS
WARMUP = CHECKED + 1  # steps before the window opens


class WindowClosed(Exception):
    """Raised from the trainer's callback to end ``fit`` at the window's end."""


class NoChip(SystemExit):
    pass


@dataclass
class Run:
    """What a per-layer metric's reader gets (``bench/metrics/<name>.py``).
    Where a family's sample is not an image, ``images_per_step`` and
    ``flops_per_image`` count its samples."""

    chips: int
    images_per_step: int
    window: tuple  # (start, end) on the host's monotonic clock
    step_ends: List[float]  # completion time of each step in the window
    spans: Dict[str, List[tuple]]  # program span name -> [(t0, t1, args)]
    stage_stats: Dict[str, Any]
    config: Dict[str, Any]
    device: Any = None  # bench.trace.DeviceTrace of the window, or None
    peaks: Dict[str, float] = field(default_factory=dict)
    family: Any = None  # the cell's bench/families/<family>.py

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def images_per_s(self) -> float:
        return len(self.step_ends) * self.images_per_step / self.seconds

    def flops_per_image(self) -> float:
        return self.family.flops_per_sample(self.config)

    def ingest_bytes_per_image(self) -> float:
        return self.family.ingest_bytes_per_sample(self.config)


def profile_options():
    """Device activity and ``TraceAnnotation`` markers (host level 1), no
    Python tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def cpu_workers(rule, cores: int) -> int:
    """The CPU stage's width: a number, or ``{"cores_less": n}``."""
    if isinstance(rule, int):
        return rule
    return max(1, cores - int(rule["cores_less"]))


def rehearsal(cell: spec.Cell) -> spec.Cell:
    """The same cell at tiny sizes, for a CPU rehearsal."""
    config, objects = cell.family.rehearse(cell.config, cell.traffic["objects"])
    loader = dict(cell.traffic["loader"], cpu_workers=2)
    traffic = dict(cell.traffic, objects=objects, loader=loader)
    return dataclasses.replace(cell, config=config, traffic=traffic)


def shutdown_loader(loader) -> None:
    """Stop what ``fit`` left running when the window closed it: the device
    ring, the pipeline iterator and the CPU stage's worker processes.

    ``Trainer.fit`` has no time limit, so the window ends it by raising from
    a callback, and nothing public stops what it started. This reaches into
    the loader's private attributes (``_device_ring``, ``_active_iter``,
    ``_cpu_pool``, the ring's ``_thread``): the one place where the benchmark
    depends on the program's internals. A time limit on ``fit`` would end it."""
    ring = getattr(loader, "_device_ring", None)
    ring = ring() if ring is not None else None
    if ring is not None:
        ring.close()
    it = getattr(loader, "_active_iter", None)
    it = it() if it is not None else None
    if it is not None:
        it.shutdown()
    pool = getattr(loader, "_cpu_pool", None)
    if pool is not None:
        pool.close()
    if ring is not None:
        ring._thread.join(timeout=10)
    for proc in multiprocessing.active_children():
        proc.terminate()
        proc.join(timeout=10)


def _probe_class():
    import jax

    from repro.train.trainer import Callback

    class Probe(Callback):
        """Copies what the check needs in the first steps, opens the window
        after the warm-up and closes it once ``seconds`` have passed."""

        def __init__(self, seconds: float, keep, on_warm=None, mark=None):
            self.seconds = seconds
            self.keep = keep  # the host copy of a batch that the check reads
            self.on_warm = on_warm  # called before the last warm-up step
            self.mark = mark  # puts a marker on the profiler's clock
            self.batches: List[tuple] = []
            self.losses: List[float] = []
            self.mu1 = self.p3 = None
            self.batch_spec = None  # shapes, types and placement of the step's batch
            self.t0: Optional[float] = None
            self.ends: List[float] = []
            self.failed = 0

        def on_train_batch_start(self, trainer, batch, idx):
            if self.batch_spec is None:
                self.batch_spec = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), batch)
            if trainer.global_step < CHECKED:
                self.batches.append(self.keep(batch))

        def on_train_batch_end(self, trainer, metrics, idx):
            now = time.monotonic()
            n = trainer.global_step
            if self.t0 is not None:
                self.ends.append(now)
                self.failed += not np.isfinite(metrics["loss"])
                if now - self.t0 >= self.seconds:
                    if self.mark:
                        self.mark("bench:window_end")
                    raise WindowClosed()
                return
            if n <= CHECKED:
                self.losses.append(float(metrics["loss"]))
            if n == 1:
                self.mu1 = check.host_leaves(trainer.state["opt"]["mu"])
            if n == CHECKED:
                self.p3 = check.host_leaves(trainer.state["params"])
            if n == WARMUP - 1 and self.on_warm:
                self.on_warm()
            if n == WARMUP:
                self.t0 = time.monotonic()
                if self.mark:
                    self.mark("bench:window_start")

    return Probe


def warm_up(trainer, batch) -> None:
    """Compile and run the step once on the family's batch of zeros (whose
    making compiles a device epilogue), before the loader starts: a copy of
    the state is donated, the real one is kept. So the loader's queues do
    not fill while the first step compiles, and the window opens on a loader
    that has run only as fast as the steps took its batches."""
    import jax
    import jax.numpy as jnp

    state = jax.tree.map(lambda x: jnp.array(x, copy=True), trainer.state)
    jax.block_until_ready(trainer.train_step(state, batch))


def step_temp_bytes(trainer, batch_spec) -> int:
    """The compiled train step's scratch, which the runtime's
    ``peak_bytes_in_use`` leaves out. The step is looked up again for the
    shapes the window ran (from the compilation cache)."""
    compiled = trainer.train_step.lower(trainer.state, batch_spec).compile()
    analysis = compiled.memory_analysis()
    return int(analysis.temp_size_in_bytes) if analysis is not None else 0


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the harness's own tests: tiny sizes on any backend, a planted fault
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--fault", default="", help=argparse.SUPPRESS)
    ap.add_argument("--pool-dir", default=storage.POOL_DIR, help=argparse.SUPPRESS)
    ap.add_argument("--keep-trace", default="", help=argparse.SUPPRESS)
    # a checkout of another benchmark (BENCHMARK.json and bench/configs,
    # traffic, limits, families), for the harness's tests
    ap.add_argument("--root", default=spec.ROOT, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def setup_jax(cell: spec.Cell, rehearse: bool):
    """The persistent compilation cache, and the devices: a TPU with as many
    chips as the cell asks for, or ``NoChip``."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(spec.BENCH, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if cell.chips != 1:
        raise NoChip(f"the cell asks for {cell.chips} chips; this harness drives one")
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found {devs[0].platform}")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, jax found {len(devs)}")
    return devs[: cell.chips]


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    args = parse_args(argv)
    root = os.path.abspath(args.root)
    cell = spec.resolve(args.workload, spec.load_benchmark(root), root)
    if args.rehearse:
        cell = rehearsal(cell)
    try:
        devices = setup_jax(cell, args.rehearse)
    except NoChip as e:
        log(str(e))
        return 2
    result = run(cell, args, devices, t_start)
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


def run(cell: spec.Cell, args, devices, t_start: float, on_check=None) -> Optional[Dict]:
    """One run; ``on_check(cell, seed, ref, prog, numbers)`` is called with
    the check's inputs (the calibration reads the control there)."""
    import jax

    from repro.config import DeliverySpec, LoaderConfig, PipelineConfig
    from repro.core import make_loader
    from repro.core.tracing import NULL_TRACER, Tracer
    from repro.train.trainer import Trainer

    from bench import faults

    cfg, tr, ld, fam = cell.config, cell.traffic, cell.traffic["loader"], cell.family
    seed = args.seed
    batch = fam.samples_per_step(cfg)
    cores = len(os.sched_getaffinity(0))
    n_cpu = cpu_workers(ld["cpu_workers"], cores)
    print(f"cell {cell.name}: {len(devices)} chip {devices[0].device_kind}, batch {batch}, "
          f"cpu_workers {n_cpu} of {cores} cores (rule {ld['cpu_workers']}), "
          f"io width {ld['num_workers'] * ld['num_fetch_workers']}", flush=True)

    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.monotonic())
        if event == "/jax/core/compile/backend_compile_duration" else None)

    pool = fam.load_pool(tr["objects"], args.pool_dir)
    print(f"pool: {len(pool)} objects, mean {pool.mean_size():.0f} bytes", flush=True)
    store = storage.build_store(tr, pool, spec.derive(seed, "store"), fam.PREFIX)
    tracer = Tracer() if args.trace else NULL_TRACER
    dataset = fam.dataset(cfg, tr, store, seed, tracer, args.fault)
    loader = make_loader(LoaderConfig(
        batch_size=batch, num_workers=int(ld["num_workers"]),
        num_fetch_workers=int(ld["num_fetch_workers"]),
        pipeline=PipelineConfig(enabled=True, reorder=ld["reorder"], cpu_workers=n_cpu,
                                cpu_executor=ld["cpu_executor"], transport=ld["transport"],
                                staging_buffers=int(ld["staging_buffers"])),
        delivery=DeliverySpec.host(), seed=spec.derive(seed, "sampler")), dataset, tracer=tracer)

    state = fam.init_state(cfg, seed)
    p0 = check.host_leaves(state["params"])
    step_fn = faults.wrap_step(args.fault, fam.make_step(cfg))
    options = fam.trainer_options(cfg, tr)

    logdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    marks: Dict[str, float] = {}

    def mark(name):
        marks[name] = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            pass

    probe = _probe_class()(
        args.seconds, fam.keep,
        on_warm=(lambda: jax.profiler.start_trace(logdir, profiler_options=profile_options()))
        if args.trace else None,
        mark=mark if args.trace else None)
    trainer = Trainer(step_fn, state, callbacks=[probe], tracer=tracer, **options)
    del state
    warm_up(trainer, fam.warm_batch(cfg, options))
    try:
        trainer.fit(loader, epochs=1)
    except WindowClosed:
        pass
    finally:
        if args.trace and probe.t0 is not None:
            jax.profiler.stop_trace()
        shutdown_loader(loader)
    if probe.t0 is None or not probe.ends:
        log("the window never opened: fewer steps than the warm-up")
        return None
    window = (probe.t0, probe.ends[-1])
    stage_stats = loader.stage_stats() or {}
    in_window = sum(1 for t in compiles if t > probe.t0)
    steps_ms = 1e3 * np.diff([window[0]] + probe.ends)
    log(f"window: {len(probe.ends)} steps in {window[1] - window[0]:.3f} s, "
        f"median step {np.median(steps_ms):.2f} ms; compiles inside it: {in_window}")
    # the device's peak: what the runtime saw in use, plus the step's scratch
    in_use = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    t_temp = time.monotonic()
    temp = step_temp_bytes(trainer, probe.batch_spec)
    log(f"memory: peak_bytes_in_use {in_use}, step temp {temp} "
        f"(looked up in {time.monotonic() - t_temp:.2f} s)")
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": jax.device_count(), "memory_peak_bytes": in_use + temp,
           "peak_bytes_in_use": in_use, "step_temp_bytes": temp}

    metrics: Dict[str, Dict] = {}
    breakdown = None
    if args.trace:
        metrics, breakdown, dev_trace = per_layer(cell, probe, window, tracer, stage_stats,
                                                  logdir, marks, batch, args.keep_trace)
        shutil.rmtree(logdir, ignore_errors=True)
        if dev_trace is not None:
            dev.update(busy_s=dev_trace.busy_s(), window_s=dev_trace.window_s())
    else:
        metrics = end_to_end(cell, probe, window, batch, t_start)

    # the check: the program's state is freed first, the reference runs after
    trainer.state = None
    del trainer, loader, dataset, store
    gc.collect()
    ref = fam.reference(cfg, tr, pool, seed)
    prog = {"batches": probe.batches, "losses": probe.losses, "mu1": probe.mu1, "p0": p0,
            "p3": probe.p3}
    numbers = fam.compare(prog, ref, cfg)
    log(f"numbers: {json.dumps(numbers)}")
    if on_check is not None:
        on_check(cell, seed, ref, prog, numbers)
    correct = check.judge(numbers, cell.limits) and probe.failed == 0
    checks = {k: {"value": numbers.get(k), "limit": lim} for k, lim in cell.limits.items()}
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": bool(correct), "attempted": len(probe.ends), "failed": probe.failed,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def end_to_end(cell, probe, window, batch, t_start) -> Dict[str, Dict]:
    ends = [window[0]] + probe.ends
    steps_ms = 1e3 * np.diff(ends)
    values = {
        "train_images_per_s": len(probe.ends) * batch / (window[1] - window[0]),
        "step_p90_ms": float(np.percentile(steps_ms, 90)),
        "setup_s": window[0] - t_start,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


def per_layer(cell, probe, window, tracer, stage_stats, logdir, marks, batch, keep):
    from bench import trace as trace_mod

    peaks_all = json.load(open(os.path.join(spec.BENCH, "peaks.json")))
    spans: Dict[str, List[tuple]] = {}
    for s in tracer.spans():
        spans.setdefault(s.name, []).append((s.t0, s.t1, s.args))
    dev_trace, peaks, breakdown = None, {}, None
    import jax

    kind = jax.devices()[0].device_kind
    if jax.devices()[0].platform == "tpu":
        if kind not in peaks_all:
            raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
        peaks = peaks_all[kind]
        dev_trace = trace_mod.load(logdir, marks["bench:window_start"],
                                   marks["bench:window_end"], keep or None)
        host = {
            "in_step_call": [(a, b) for a, b, _ in spans.get("run_training_batch", [])],
            "h2d": [(a, b) for a, b, _ in spans.get("batch_to_device", [])],
        }
        host["waiting_for_batch"] = trace_mod.gaps_between(host["in_step_call"], *window)
        breakdown = trace_mod.breakdown(dev_trace, host)
    run = Run(chips=1, images_per_step=batch, window=window, step_ends=probe.ends,
              spans=spans, stage_stats=stage_stats, config=cell.config, device=dev_trace,
              peaks=peaks, family=cell.family)
    metrics = {}
    for m in cell.per_layer:
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, breakdown, dev_trace
