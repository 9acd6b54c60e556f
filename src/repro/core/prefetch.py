"""Device prefetch ring — the TPU analogue of pinned memory + async H2D.

Wraps a host-batch iterator; a background thread `jax.device_put`s the next
``depth`` batches (optionally with a NamedSharding so each host only
materializes its addressable shards) while the current step runs.  Records
``batch_to_device`` spans (paper Fig. 1/2 magenta lane).

``depth`` is adjustable live (:meth:`set_depth`) for the online autotuner:
the in-flight window is gated by an :class:`AdjustableSemaphore` rather than
the queue's fixed ``maxsize``, so deepening the ring takes effect immediately
and shrinking drains naturally as the consumer pulls batches.

Zero-copy extensions (PR 7): batches collated into pooled staging buffers
(:mod:`repro.core.staging`) are released back to their pool the moment the
transfer lands, and ``ingest_fn`` runs a jitted on-device epilogue (the
fused ``kernels/ingest_norm`` cast+normalize) right after the put — raw
uint8 crosses the bus, the f32 batch is born on device.

With a live tracer, ``batch_to_device`` is also a
``jax.profiler.TraceAnnotation`` (:meth:`Tracer.annotated_span`): the ring's
transfers show on a profiler trace beside the device's operations.

A host array of rank above 2 crosses to a single device as rows
(:func:`_put_rows`) and takes its shape back there.
"""
from __future__ import annotations

import math
import queue
import threading
from typing import Any, Iterator, Optional

import jax
import numpy as np

from repro.core.fetcher import AdjustableSemaphore
from repro.core.tracing import BATCH_TO_DEVICE, NULL_TRACER, Tracer


class _End:
    pass


class _Err:
    def __init__(self, exc: BaseException) -> None:
        self.exc = exc


def _put_rows(x: Any) -> Any:
    """``jax.device_put`` of one leaf onto the default device.  A host array
    of rank above 2 crosses as ``(rows, -1)`` and is reshaped on the device:
    the TPU runtime lays such an array out on the host before the copy, and
    a small minor dimension makes that slow.  For 256 HWC uint8 images of
    224 px (38.5 MB) on a TPU v5e: 42 ms as (256, 224, 224, 3), 8 ms as
    rows; and 2.1 s while ``jax.profiler`` traces with its host tracer on,
    which leaves the rows' 8 ms as it is."""
    if isinstance(x, np.ndarray) and x.ndim > 2:
        rows = x.reshape(x.shape[0], math.prod(x.shape[1:]))
        return jax.device_put(rows).reshape(x.shape)
    return jax.device_put(x)


class DevicePrefetchRing:
    def __init__(
        self,
        it: Iterator[Any],
        *,
        depth: int = 2,
        max_depth: Optional[int] = None,
        sharding: Optional[Any] = None,
        transfer: bool = True,
        tracer: Tracer = NULL_TRACER,
        ingest_fn: Optional[Any] = None,
    ) -> None:
        self.it = it
        depth = max(1, depth)
        self.max_depth = max(depth, max_depth or depth)
        # sharding may be a jax Sharding applied uniformly, or a callable
        # leaf -> Sharding for pytrees whose leaves differ in rank (a 1-d
        # label next to a 4-d image can't share one PartitionSpec)
        self.sharding = sharding
        # transfer=False turns the ring into pure pacing: sharded delivery
        # hands over batches that are ALREADY device-resident, and a
        # device_put here would gather the global array back to one device
        self.transfer = transfer
        self.tracer = tracer
        # on-device ingest epilogue: a jitted batch -> batch callable (see
        # repro.kernels.ingest_norm.make_ingest_fn) applied after the put
        self.ingest_fn = ingest_fn
        self._slots = AdjustableSemaphore(depth)
        self._q: "queue.Queue" = queue.Queue()  # window bounded by _slots
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="device-prefetch", daemon=True)
        self._thread.start()

    @property
    def depth(self) -> int:
        return self._slots.limit

    def set_depth(self, depth: int) -> int:
        """Adjust the in-flight window; returns the applied (clamped) value."""
        d = max(1, min(int(depth), self.max_depth))
        self._slots.set_limit(d)
        return d

    def _put_device(self, batch: Any) -> Any:
        if not self.transfer:
            if self.ingest_fn is not None:
                batch = self.ingest_fn(batch)
            return batch
        # dict SUBCLASSES (StagedBatch, ShmItem) are leaves to jax.tree —
        # transfer a plain-dict view so device_put sees the arrays; `batch`
        # keeps the staged identity for the release below
        host = dict(batch) if isinstance(batch, dict) and type(batch) is not dict else batch
        with self.tracer.annotated_span(BATCH_TO_DEVICE):
            if callable(self.sharding):
                dev = jax.tree.map(
                    lambda x: jax.device_put(x, self.sharding(x)), host
                )
            elif self.sharding is not None:
                dev = jax.tree.map(lambda x: jax.device_put(x, self.sharding), host)
            else:
                dev = jax.tree.map(_put_rows, host)
            # block until the transfer lands so the span is honest
            jax.tree.map(
                lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
                dev,
            )
        # the host bytes are on device: a staged batch's pooled buffers are
        # reusable from here — unless the backend's device_put was zero-copy
        # (XLA CPU), which release_after detects and detaches instead
        release = getattr(batch, "release_after", None)
        if callable(release):
            release(dev)
        if self.ingest_fn is not None:
            # fused on-device epilogue (cast + scale + mean/std): runs async
            # on the accelerator stream; the training step's own data
            # dependency orders it, so no blocking here
            dev = self.ingest_fn(dev)
        return dev

    def _acquire_slot(self) -> bool:
        """Wait for a free ring slot, polling the stop flag."""
        while not self._stop.is_set():
            if self._slots.acquire(timeout=0.1):
                return True
        return False

    def _run(self) -> None:
        try:
            for batch in self.it:
                if self._stop.is_set():
                    return
                dev = self._put_device(batch)
                # slot acquired AFTER the transfer, matching the fixed-queue
                # behaviour (depth queued + 1 transferred-and-waiting)
                if not self._acquire_slot():
                    return
                self._q.put(dev)
            self._q.put(_End())
        except BaseException as e:  # propagate
            self._q.put(_Err(e))

    def __iter__(self) -> "DevicePrefetchRing":
        return self

    def __next__(self) -> Any:
        item = self._q.get()
        if isinstance(item, _End):
            raise StopIteration
        if isinstance(item, _Err):
            raise item.exc
        self._slots.release()
        return item

    def close(self) -> None:
        self._stop.set()
