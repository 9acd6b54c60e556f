"""Trainer with hooks/callbacks — the "Lightning analogue" (paper §A.3).

The paper found Lightning's callback/logging machinery (GPUStatsMonitor +
aggressive ``log_every_n_steps``) responsible for a large Torch-vs-Lightning
gap.  We reproduce the mechanism: a raw loop (:func:`raw_train_loop`, the
"Torch" path) vs :class:`Trainer` (hooks before/after every batch, logging
callbacks with configurable frequency/cost).

Both paths share the jitted step, the ConcurrentDataLoader and the device
prefetch ring, and record the paper's span lanes so Table-3 style stats come
out of the same tracer: ``loader_wait`` around each ``next(ring)`` and
``run_training_batch`` around each step, both also on the profiler's clock
(:meth:`~repro.core.tracing.Tracer.annotated_span`).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional

import jax

from repro.core.prefetch import DevicePrefetchRing
from repro.core.tracing import (
    LOADER_WAIT,
    NULL_TRACER,
    RUN_TRAINING_BATCH,
    Tracer,
)
from repro.core.utilization import recent_busy_fraction


class Callback:
    def on_train_start(self, trainer: "Trainer") -> None: ...
    def on_epoch_start(self, trainer: "Trainer", epoch: int) -> None: ...
    def on_train_batch_start(self, trainer: "Trainer", batch: Any, idx: int) -> None: ...
    def on_train_batch_end(self, trainer: "Trainer", metrics: Dict, idx: int) -> None: ...
    def on_epoch_end(self, trainer: "Trainer", epoch: int) -> None: ...
    def on_train_end(self, trainer: "Trainer") -> None: ...


class LoggingCallback(Callback):
    """Emulates the paper's GPUStatsMonitor-style logger: every call burns
    ``cost_s`` of host time (the 'slightly too aggressive logging')."""

    def __init__(self, log_every_n_steps: int = 10, cost_s: float = 0.0,
                 sink: Optional[Callable[[str], None]] = None) -> None:
        self.every = max(log_every_n_steps, 1)
        self.cost_s = cost_s
        self.sink = sink or (lambda s: None)
        self.lines: List[str] = []

    def on_train_batch_end(self, trainer, metrics, idx) -> None:
        if idx % self.every == 0:
            if self.cost_s:
                time.sleep(self.cost_s)
            line = f"step={trainer.global_step} " + " ".join(
                f"{k}={float(v):.4f}" for k, v in metrics.items()
            )
            self.lines.append(line)
            self.sink(line)


class CheckpointCallback(Callback):
    def __init__(self, manager, every_steps: int, loader=None, blocking: bool = False):
        self.manager = manager
        self.every = every_steps
        self.loader = loader
        self.blocking = blocking

    def on_train_batch_end(self, trainer, metrics, idx) -> None:
        if self.every and trainer.global_step % self.every == 0:
            extra = {}
            if self.loader is not None:
                # Cursor derived from the TRAINER's position, not the
                # loader's: the device prefetch ring consumes batches ahead
                # of the training step, so loader.state_dict() would skip
                # the in-flight batches on restart.  One step == one batch.
                n = len(self.loader)
                extra = {"loader": {
                    "epoch": trainer.global_step // n,
                    "next_batch": trainer.global_step % n,
                }}
            self.manager.save(
                trainer.global_step, trainer.state, extra_meta=extra,
                blocking=self.blocking,
            )


@dataclass
class TrainResult:
    steps: int
    epochs: int
    wall_s: float
    last_metrics: Dict[str, float] = field(default_factory=dict)
    history: List[Dict[str, float]] = field(default_factory=list)


def _make_ring(loader, depth: int, tracer, ingest_fn=None) -> DevicePrefetchRing:
    """Build the per-epoch device prefetch ring; when the loader carries an
    autotuner, register the ring's depth as a live knob (sized so it has
    headroom up to the configured bound) and wire the accelerator-utilization
    signal so the controller stops buying loader throughput the training step
    can't eat (AutotuneConfig.util_gate)."""
    auto = getattr(loader, "autotuner", None)
    max_depth = depth
    if auto is not None:
        max_depth = max(depth, auto.cfg.max_device_prefetch)
    ring = DevicePrefetchRing(
        iter(loader), depth=depth, max_depth=max_depth,
        # sharded delivery hands over device-resident global arrays; the
        # ring then only paces (a device_put would gather them back)
        transfer=not getattr(loader, "delivers_device_batches", False),
        tracer=tracer,
        # on-device epilogue for epilogue="device" datasets: runs the fused
        # ingest_norm cast+normalize right after the put, off the host
        ingest_fn=ingest_fn,
    )
    if auto is not None:
        # iter(loader) above re-bound the loader knobs; the ring knob rides
        # along for this epoch and is dropped at the next re-bind
        auto.attach_ring(ring)
        if tracer is not NULL_TRACER and auto.util_fn is None:
            auto.util_fn = lambda: recent_busy_fraction(tracer)
    note = getattr(loader, "note_device_ring", None)
    if callable(note):
        # the ring is the staged pipeline's final (device-prefetch) stage;
        # registering it folds its depth into loader.stage_stats()
        note(ring)
    return ring


_END = object()


def _waited(ring: DevicePrefetchRing, tracer: Tracer, step: int) -> Iterator[Any]:
    """Iterate the device ring, each ``next`` a ``loader_wait`` span tagged
    with the step that gets the batch (the last is the wait for the epoch's
    end)."""
    while True:
        with tracer.annotated_span(LOADER_WAIT, step=step):
            batch = next(ring, _END)
        if batch is _END:
            return
        yield batch
        step += 1


def _release_coordination(loader) -> None:
    """End-of-fit courtesy for multi-host runs: hand back any held up-probe
    lease so co-located hosts don't wait out the crash TTL before climbing."""
    release = getattr(loader, "release_coordination", None)
    if callable(release):
        release()


class Trainer:
    def __init__(
        self,
        train_step: Callable,
        state: Any,
        *,
        callbacks: Optional[List[Callback]] = None,
        tracer: Tracer = NULL_TRACER,
        device_prefetch: int = 2,
        jit: bool = True,
        donate: bool = True,
        ingest_fn: Optional[Callable] = None,
    ) -> None:
        self.train_step = (
            jax.jit(train_step, donate_argnums=(0,)) if jit and donate
            else jax.jit(train_step) if jit
            else train_step
        )
        self.state = state
        self.callbacks = callbacks or []
        self.tracer = tracer
        self.device_prefetch = device_prefetch
        # dict -> dict device-side batch epilogue (see
        # repro.kernels.ingest_norm.ops.make_ingest_fn); None = host epilogue
        self.ingest_fn = ingest_fn
        self.global_step = 0

    def _hook(self, name: str, *args) -> None:
        for cb in self.callbacks:
            getattr(cb, name)(self, *args)

    def fit(
        self,
        loader: Iterable,
        epochs: int = 1,
        max_steps: Optional[int] = None,
        start_epoch: int = 0,
    ) -> TrainResult:
        t0 = time.time()
        self._hook("on_train_start")
        history: List[Dict[str, float]] = []
        metrics: Dict[str, float] = {}
        done = False
        for epoch in range(start_epoch, epochs):
            if hasattr(loader, "set_epoch") and epoch != start_epoch:
                loader.set_epoch(epoch)
            self._hook("on_epoch_start", epoch)
            ring = _make_ring(loader, self.device_prefetch, self.tracer,
                              ingest_fn=self.ingest_fn)
            for i, batch in enumerate(_waited(ring, self.tracer, self.global_step)):
                self._hook("on_train_batch_start", batch, i)
                with self.tracer.annotated_span(RUN_TRAINING_BATCH, step=self.global_step):
                    self.state, m = self.train_step(self.state, batch)
                    m = jax.tree.map(float, jax.device_get(m))
                self.global_step += 1
                metrics = m
                history.append(m)
                self._hook("on_train_batch_end", m, i)
                if max_steps is not None and self.global_step >= max_steps:
                    done = True
                    break
            ring.close()
            self._hook("on_epoch_end", epoch)
            if done:
                break
        self._hook("on_train_end")
        _release_coordination(loader)
        return TrainResult(
            steps=self.global_step,
            epochs=epoch + 1,
            wall_s=time.time() - t0,
            last_metrics=metrics,
            history=history,
        )


def raw_train_loop(
    train_step: Callable,
    state: Any,
    loader: Iterable,
    *,
    epochs: int = 1,
    max_steps: Optional[int] = None,
    tracer: Tracer = NULL_TRACER,
    device_prefetch: int = 2,
    jit: bool = True,
    ingest_fn: Optional[Callable] = None,
) -> TrainResult:
    """The 'pure Torch' path: no hooks, no callbacks, same jitted step.
    Pass ``jit=False`` when ``train_step`` is already jitted (lets callers
    share one compiled executable across runs)."""
    step_fn = jax.jit(train_step, donate_argnums=(0,)) if jit else train_step
    t0 = time.time()
    steps = 0
    metrics: Dict[str, float] = {}
    history = []
    for epoch in range(epochs):
        if hasattr(loader, "set_epoch") and epoch:
            loader.set_epoch(epoch)
        ring = _make_ring(loader, device_prefetch, tracer, ingest_fn=ingest_fn)
        for batch in _waited(ring, tracer, steps):
            with tracer.annotated_span(RUN_TRAINING_BATCH, step=steps):
                state, m = step_fn(state, batch)
                metrics = jax.tree.map(float, jax.device_get(m))
            history.append(metrics)
            steps += 1
            if max_steps is not None and steps >= max_steps:
                ring.close()
                _release_coordination(loader)
                return TrainResult(steps, epoch + 1, time.time() - t0, metrics, history)
        ring.close()
    _release_coordination(loader)
    return TrainResult(steps, epochs, time.time() - t0, metrics, history)
