"""Staged streaming pipeline — the loader behind ``LoaderConfig.pipeline``.

The legacy worker/fetcher path treats ``dataset[i]`` as one opaque unit, so
network fetch, decode and augmentation all run on the same fetch thread:
slow CPU preprocessing blocks IO concurrency, a straggler GET parks the
CPU, and a worker's whole thread pool idles through the tail of every batch
(head-of-line blocking at the batch boundary).  This module splits the item
path into an explicit stage graph::

    sampler -> [fetch-raw | IO executor] -> bounded queue
            -> [decode -> augment | CPU executor] -> completion queue
            -> [assembler: collate] -> consumer (-> device-prefetch ring)

* **IO executor** — thread pool or asyncio event loop (``LoaderConfig.impl``)
  whose effective concurrency is an :class:`AdjustableSemaphore` gate, with
  optional hedged duplicates for straggler GETs (reusing
  :class:`~repro.core.fetcher.HedgeTracker`).
* **CPU executor** — ``decode_raw`` + ``augment_item`` on a separate gated
  executor (datasets exposing the split path; see
  :class:`repro.data.dataset.MapDataset`): a thread pool
  (``LoaderConfig.cpu_executor="thread"``, right for GIL-releasing C
  decoders) or a spawn-based worker-process pool (``"process"``, the GIL
  escape for pure-Python decoders — Appendix A.4's ceiling; requires a
  picklable dataset, persists across epochs, respawns crashed workers and
  retries only their in-flight sample).  Datasets that cannot split fall
  back to the monolithic ``__getitem__`` on the IO executor.
* **Out-of-order completion** — samples finish in whatever order storage and
  CPU allow; the assembler composes batches per ``LoaderConfig.reorder``:
  ``"strict"`` rebuilds exactly the legacy stream (same samples, same order,
  bit-identical), ``"window"`` fills each aligned group of
  ``reorder_window`` batch slots with whichever of the group's samples
  finish first, so a straggler only delays the *last* batch of its group.
* **Per-stage observability** — every sample records ``io_admit`` (waiting
  for an IO slot), ``stage_fetch``, ``io_handoff`` (its slot held while the
  fetch->decode queue takes it), ``stage_decode`` / ``stage_augment`` spans
  and every batch a ``stage_collate`` span; inter-stage queues track occupancy
  (:meth:`_PipelineIter.stage_stats`), which is how ``bench_pipeline``
  proves decode/IO overlap.
* **Per-stage tuning** — io workers, cpu workers, the outstanding sample
  window and the fetch->decode queue depth are live knobs registered with
  the loader's :class:`~repro.core.autotune.AutotuneController`
  (:func:`~repro.core.autotune.build_pipeline_knobs`).
"""
from __future__ import annotations

import asyncio
import math
import multiprocessing
import os
import pickle
import queue
import threading
import time
import weakref
from collections import deque
from multiprocessing.connection import wait as _mp_wait
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.core import shm as shm_mod
from repro.core.fetcher import (
    AdjustableSemaphore,
    aretry_transient,
    retry_transient,
)
from repro.core.sampler import BatchIndices
from repro.core.tracing import (
    BYTES_COPIED,
    IO_ADMIT,
    IO_HANDOFF,
    IO_NARROWED,
    IO_WIDENED,
    NULL_TRACER,
    STAGE_AUGMENT,
    STAGE_COLLATE,
    STAGE_DECODE,
    STAGE_FETCH,
)


class _Sample:
    """One flattened unit of work flowing through the stage graph."""

    __slots__ = ("batch_id", "pos", "index", "raw", "t_submit")

    def __init__(self, batch_id: int, pos: int, index: int) -> None:
        self.batch_id = batch_id
        self.pos = pos
        self.index = index
        self.raw: Any = None
        self.t_submit = 0.0  # monotonic time of _IOStage.submit


class _Failure:
    """Exception carrier routed through the completion queue."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException) -> None:
        self.exc = exc


class _Composed:
    """Completion-queue token for a fully composed device-sharded batch
    (sharded delivery, :mod:`repro.core.delivery`).  Defined here rather
    than in the delivery module so the pipeline's hot loop can type-check
    it without importing jax."""

    __slots__ = ("batch_id",)

    def __init__(self, batch_id: int) -> None:
        self.batch_id = batch_id


class _BoundedQ:
    """FIFO whose capacity is an :class:`AdjustableSemaphore`, so queue depth
    is a live autotune knob.  ``put`` blocks while the downstream stage is
    full (polling the pipeline stop event) — that stall, propagating back to
    the IO gate, is the pipeline's backpressure.  Tracks occupancy so the
    bottleneck stage is visible (a full fetch->decode queue = CPU-bound, an
    empty one = IO-bound)."""

    def __init__(self, depth: int, stop: threading.Event) -> None:
        self._q: "queue.Queue" = queue.Queue()
        self._cap = AdjustableSemaphore(max(1, depth))
        self._stop = stop
        self._lock = threading.Lock()
        self._occ_sum = 0
        self._occ_n = 0
        self._occ_max = 0
        # puts, and puts that found the queue full (the taking stage was
        # behind): the IO width controller's backpressure signal
        self.puts = 0
        self.full_puts = 0

    @property
    def depth(self) -> int:
        return self._cap.limit

    def resize(self, depth: int, hi: int) -> int:
        d = max(1, min(int(depth), hi))
        self._cap.set_limit(d)
        return d

    def _note(self, put: bool = False) -> None:
        size = self._q.qsize()
        with self._lock:
            self._occ_sum += size
            self._occ_n += 1
            self._occ_max = max(self._occ_max, size)
            self.puts += put

    def put(self, item: Any) -> bool:
        if not self._cap.acquire(timeout=0):
            with self._lock:
                self.full_puts += 1
            while not self._cap.acquire(timeout=0.1):
                if self._stop.is_set():
                    return False
        self._q.put(item)
        self._note(put=True)
        return True

    def get(self, timeout: float = 0.1) -> Any:
        item = self._q.get(timeout=timeout)  # queue.Empty passes through
        self._cap.release()
        self._note()
        return item

    def occupancy(self) -> Dict[str, float]:
        with self._lock:
            mean = self._occ_sum / self._occ_n if self._occ_n else 0.0
            return {
                "depth": self._cap.limit,
                "now": self._q.qsize(),
                "mean": round(mean, 2),
                "max": self._occ_max,
            }


# ---------------------------------------------------------------------------
# IO stage
# ---------------------------------------------------------------------------


class _IOStage:
    """Fetch-raw stage: a dedicated IO executor (thread pool or asyncio loop)
    gated by an :class:`AdjustableSemaphore`.

    Admission is caller-side: :meth:`submit` parks samples in a pending deque
    and ``_kick`` moves them onto the executor only when a gate permit is
    free, so idle executor threads never pile up behind the gate and a
    ``resize`` takes effect at the next admission.  The gate permit is held
    across the fetch AND the (possibly blocking) hand-off into the
    fetch->decode queue: when decode backs up, IO concurrency drains to zero
    instead of buffering unboundedly.

    With a live tracer each sample records ``io_admit`` (submit to permit)
    and ``io_handoff`` (end of the GET to the hand-off's acceptance, the
    permit held but not fetching); with ``NULL_TRACER`` admission costs one
    ``time.monotonic`` per sample.

    Threaded mode runs the GETs on ``pipe-io`` threads that take admitted
    samples from a work queue; they are started as the gate's limit first
    reaches each width, up to ``hard_cap``, plus two, so the thread count
    follows the widest the gate has been, not the hard cap.

    Hedging (both modes, reusing :class:`HedgeTracker`): the assembler
    loop calls :meth:`hedge_scan`; any in-flight fetch older than the p95
    deadline gets one ungated duplicate — on the two headroom threads
    (threaded) or as an extra coroutine on the event loop (asyncio) — and
    the first completion wins via the shared ``_inflight`` pop.
    """

    def __init__(
        self,
        dataset,
        *,
        mode: str,  # "threaded" | "asyncio"
        width: int,
        hard_cap: int,
        split: bool,
        decode_q: _BoundedQ,
        done_q: "queue.Queue",
        stop: threading.Event,
        tracer,
        hedge=None,
    ) -> None:
        self.dataset = dataset
        self.mode = mode
        self.split = split
        self.decode_q = decode_q
        self.done_q = done_q
        self.stop = stop
        self.tracer = tracer
        self._traced = tracer is not NULL_TRACER
        self.hedge = hedge
        self.hard_cap = max(width, hard_cap)
        self.gate = AdjustableSemaphore(width)
        self._pending: deque = deque()
        self._lock = threading.Lock()
        # in-flight registry: id(sample) -> (sample, t0).  Doubles as the
        # first-response-wins arbiter for hedged fetches: whichever copy
        # pops the entry owns the sample; the loser finds it gone and drops
        # its result.
        self._inflight: Dict[int, Tuple[_Sample, float]] = {}
        # primary GET seconds since the width controller last read them
        # (None: no controller, nothing recorded)
        self.get_s: Optional[List[float]] = None
        if mode == "asyncio":
            self._loop = asyncio.new_event_loop()
            self._thread = threading.Thread(
                target=self._loop.run_forever, name="pipe-io-loop", daemon=True
            )
            self._thread.start()
        else:
            self._loop = None
            # (fn, sample) items, None ends a thread
            self._work: "queue.SimpleQueue" = queue.SimpleQueue()
            self._threads: List[threading.Thread] = []
            self._ensure_threads()

    def _ensure_threads(self) -> None:
        """Start IO threads up to the gate's limit (at most the hard cap),
        plus two headroom threads so hedge duplicates can run while every
        gated slot is busy with stragglers."""
        with self._lock:
            while len(self._threads) < min(self.gate.limit, self.hard_cap) + 2:
                t = threading.Thread(target=self._io_thread, daemon=True,
                                     name=f"pipe-io-{len(self._threads)}")
                self._threads.append(t)
                t.start()

    def _io_thread(self) -> None:
        while (item := self._work.get()) is not None:
            fn, s = item
            fn(s)

    # -- admission -----------------------------------------------------------
    def submit(self, sample: _Sample) -> None:
        sample.t_submit = time.monotonic()
        with self._lock:
            self._pending.append(sample)
        self._kick()

    def _kick(self) -> None:
        while True:
            with self._lock:
                if (self.stop.is_set() or not self._pending
                        or not self.gate.acquire(timeout=0)):
                    return
                s = self._pending.popleft()
            if self._traced:
                self.tracer.record(IO_ADMIT, s.t_submit, time.monotonic(),
                                   index=s.index, batch_id=s.batch_id)
            if self._loop is not None:
                asyncio.run_coroutine_threadsafe(self._afetch(s), self._loop)
            else:
                self._work.put((self._run_fetch, s))

    def resize(self, width: int) -> int:
        w = max(1, min(int(width), self.hard_cap))
        self.gate.set_limit(w)
        if self._loop is None:
            self._ensure_threads()
        self._kick()  # a raised limit admits parked samples immediately
        return w

    def parked(self) -> int:
        """Samples submitted but still waiting for a gate permit."""
        return len(self._pending)

    def oldest_get_t0(self) -> Optional[float]:
        """Start of the oldest primary GET in flight, or None (a hedged
        straggler's entry is re-armed into the future, so it does not count)."""
        with self._lock:
            return min((t for _, t in self._inflight.values()), default=None)

    def _observe(self, dt: float) -> None:
        """Feed a primary GET's duration to hedging and the width controller."""
        if self.hedge is not None:
            self.hedge.observe(dt)
        gets = self.get_s
        if gets is not None:
            gets.append(dt)

    # -- completion (first response wins when hedged) ------------------------
    def _complete(self, s: _Sample, raw: Any, t_got: float, hedge: bool = False) -> bool:
        """Route a finished fetch (its GET ended at ``t_got``) downstream;
        returns False when the other copy of a hedged fetch already claimed
        the sample."""
        with self._lock:
            if self._inflight.pop(id(s), None) is None:
                return False
        self._hand_off(s, raw, t_got, hedge)
        return True

    def _hand_off(self, s: _Sample, raw: Any, t_got: float, hedge: bool) -> None:
        """Pass a fetched sample downstream, blocking while the fetch->decode
        queue is full, and record the ``io_handoff`` span."""
        if self.split:
            s.raw = raw
            self.decode_q.put(s)
        else:
            self.done_q.put((s, raw))  # raw IS the finished item (monolithic)
        if self._traced:
            args = {"hedge": True} if hedge else {}
            self.tracer.record(IO_HANDOFF, t_got, time.monotonic(),
                               index=s.index, batch_id=s.batch_id, **args)

    def _fail(self, s: _Sample, exc: BaseException) -> None:
        with self._lock:
            if self._inflight.pop(id(s), None) is None:
                return  # a hedge duplicate already delivered this sample
        self.done_q.put((s, _Failure(exc)))

    # -- threaded fetch ------------------------------------------------------
    def _fetch_value(self, s: _Sample) -> Any:
        if self.split:
            return retry_transient(self.dataset.get_raw, s.index)
        return retry_transient(self.dataset.__getitem__, s.index)

    def _run_fetch(self, s: _Sample) -> None:
        if self.stop.is_set():
            self.gate.release()  # queued before shutdown: not fetched
            return
        t0 = time.monotonic()
        with self._lock:
            self._inflight[id(s)] = (s, t0)
        try:
            raw = self._fetch_value(s)
            t1 = time.monotonic()
            self.tracer.record(STAGE_FETCH, t0, t1, index=s.index,
                               batch_id=s.batch_id)
            self._observe(t1 - t0)
            self._complete(s, raw, t1)
        except BaseException as e:
            self._fail(s, e)
        finally:
            self.gate.release()
            self._kick()

    def _run_hedge(self, s: _Sample) -> None:
        """Ungated duplicate of a straggling fetch; first completion wins."""
        t0 = time.monotonic()
        try:
            raw = self._fetch_value(s)
            t1 = time.monotonic()
            self.tracer.record(STAGE_FETCH, t0, t1,
                               index=s.index, batch_id=s.batch_id, hedge=True)
            if self._complete(s, raw, t1, hedge=True) and self.hedge is not None:
                self.hedge.hedges_won += 1
        except BaseException:
            pass  # the original is still in flight; let it decide the outcome

    def hedge_scan(self) -> None:
        """Issue duplicates for fetches past the p95 deadline (called from
        the assembler loop, so hedging needs no dedicated timer thread)."""
        if self.hedge is None or not self.hedge.enabled:
            return
        deadline = self.hedge.deadline()
        now = time.monotonic()
        stale: List[_Sample] = []
        with self._lock:
            for s, t0 in self._inflight.values():
                if now - t0 > deadline:
                    stale.append(s)
            for s in stale:  # re-arm so one straggler hedges only once
                self._inflight[id(s)] = (s, now + 3600.0)
        for s in stale:
            self.hedge.hedges_issued += 1
            if self._loop is not None:
                # asyncio: the duplicate is one more coroutine on the loop,
                # ungated like the threaded pool's headroom duplicates
                asyncio.run_coroutine_threadsafe(self._ahedge(s), self._loop)
            else:
                self._work.put((self._run_hedge, s))

    # -- asyncio fetch -------------------------------------------------------
    async def _acomplete(self, s: _Sample, raw: Any, t_got: float,
                         hedge: bool = False) -> bool:
        """Async mirror of :meth:`_complete`: same first-response-wins pop,
        but the (possibly blocking) decode-queue hand-off runs in an executor
        so other in-flight GETs keep progressing on the event loop."""
        with self._lock:
            if self._inflight.pop(id(s), None) is None:
                return False  # the other copy of a hedged fetch already won
        if self.split:
            await asyncio.get_running_loop().run_in_executor(
                None, self._hand_off, s, raw, t_got, hedge
            )
        else:
            self._hand_off(s, raw, t_got, hedge)
        return True

    async def _afetch(self, s: _Sample) -> None:
        t0 = time.monotonic()
        with self._lock:
            self._inflight[id(s)] = (s, t0)
        try:
            fetch = self.dataset.aget_raw if self.split else self.dataset.aget_item
            raw = await aretry_transient(fetch, s.index)
            t1 = time.monotonic()
            self.tracer.record(STAGE_FETCH, t0, t1,
                               index=s.index, batch_id=s.batch_id)
            self._observe(t1 - t0)
            await self._acomplete(s, raw, t1)
        except BaseException as e:
            self._fail(s, e)
        finally:
            self.gate.release()
            self._kick()

    async def _ahedge(self, s: _Sample) -> None:
        """Ungated asyncio duplicate of a straggling fetch; first wins."""
        t0 = time.monotonic()
        try:
            fetch = self.dataset.aget_raw if self.split else self.dataset.aget_item
            raw = await aretry_transient(fetch, s.index)
            t1 = time.monotonic()
            self.tracer.record(STAGE_FETCH, t0, t1,
                               index=s.index, batch_id=s.batch_id, hedge=True)
            if await self._acomplete(s, raw, t1, hedge=True) and self.hedge is not None:
                self.hedge.hedges_won += 1
        except BaseException:
            pass  # the original is still in flight; let it decide the outcome

    def close(self) -> None:
        if self._loop is None:
            # the stop event is set: nothing more is admitted, samples
            # already queued give their permits back unfetched, and each
            # thread ends after its current GET (a blocked hand-off gives
            # up within its 0.1 s poll); join them, a widened gate may have
            # started hundreds
            with self._lock:
                threads = list(self._threads)
            for _ in threads:
                self._work.put(None)
            deadline = time.monotonic() + 5.0
            me = threading.current_thread()
            for t in threads:
                if t is not me:
                    t.join(timeout=max(0.0, deadline - time.monotonic()))
        if self._loop is not None:
            def _cancel_and_stop() -> None:
                # cancel in-flight fetch/hedge coroutines before stopping so
                # loop teardown doesn't destroy pending tasks mid-await
                for task in asyncio.all_tasks(self._loop):
                    task.cancel()
                self._loop.call_soon(self._loop.stop)

            self._loop.call_soon_threadsafe(_cancel_and_stop)
            self._thread.join(timeout=5)
            if not self._loop.is_running():
                self._loop.close()


# The derived IO width's control rule (_IOWidth).  Each constant is one
# decision of the rule:
# - a widening step of 1.25x takes a 64-slot seed to 256 in seven steps,
#   while one step past the store's connection pool overshoots it by at
#   most a quarter;
IO_WIDEN = 1.25
# - a short-window median GET this many times the lowest one seen means the
#   store queues requests: more GETs in flight add wait, not throughput.
#   A store that queues in order doubles its median GET at twice its
#   connection pool; below 2x lie what shared bandwidth adds (an S3-like
#   store's median GET grows 1.23x from 64 to 256 in flight), the low bias
#   of the lowest of many noisy medians (~12%) and the interpreter lock's
#   share of a GET on a busy host;
IO_LATENCY_TOL = 2.0
# - a GET still in flight this many times the window's 90th percentile GET
#   is starved: a connection pool whose waiters are not served in order (a
#   thread that just released a connection takes it again) leaves the
#   median untouched while the GETs beyond its size wait for seconds.  Six
#   times the p90 of lognormal GETs (sigma 0.5) is 11.4x their median, which
#   a healthy GET exceeds once in 1.8 million.  The p90, not the longest:
#   starved GETs that do finish would raise the longest;
IO_STALL = 6.0
# - a window lasts two median GETs, so that most GETs of the window after a
#   change began at the new width (the window right after a change is not
#   judged), and holds at least 32 GETs: a median of fewer is too noisy to
#   compare with the tolerance (lognormal, sigma 0.5: an 11% error at 32);
IO_WINDOW_GETS = 2.0
IO_MIN_GETS = 32
# - the CPU stage keeps up (waits for samples) while fewer than this share
#   of the window's hand-offs found the fetch->decode queue full.  A stage
#   that keeps up drains the queue, so hand-offs never wait; one that sets
#   the pace lets it fill, and then nearly every hand-off waits.  (Takes
#   that found the queue empty are no such signal: the process stage's pump
#   collects samples between worker results, and on a TPU v5e host read
#   0.17-0.38 empty takes per take while IO set the pace.)
IO_FULL_SHARE = 0.5


class _IOWidth:
    """Sizes the IO gate from what the IO stage observes, where the width is
    derived (``PipelineConfig.io_workers == 0``) and no autotuner owns it.

    A latency-gradient concurrency limit (TCP Vegas; Netflix's gradient
    limiter), by Little's law: the GETs that must be in flight are the rate
    the CPU stage can absorb times the GET time.  The assembler calls
    :meth:`update` as it loops, also while it waits for a batch that a
    starved GET holds back; once per window (:data:`IO_WINDOW_GETS`,
    :data:`IO_MIN_GETS`) of primary GETs it

    * widens by :data:`IO_WIDEN`, up to ``ceiling`` (the samples the loader
      keeps outstanding), while samples wait for an IO slot, the CPU stage
      keeps up (:data:`IO_FULL_SHARE`) and GET latency stays within
      :data:`IO_LATENCY_TOL`: the median GET of the lowest median seen, and
      the oldest GET in flight of the window's 90th percentile GET;
    * narrows by the same factor, never below the seed, when the median GET
      exceeds that tolerance;
    * when a GET in flight is starved (:data:`IO_STALL`), narrows one step
      below the width at which that GET began (or the current width, if
      lower), and widens no further than that for the rest of the epoch;
    * otherwise holds.

    The window after a change is not judged: its GETs began at the old
    width.  On a local or in-memory store the CPU stage sets the pace and
    the fetch->decode queue stays full, so the width stays at the seed.
    Hedged duplicates are not read: the IO stage feeds it primary GET times
    only."""

    def __init__(self, io: _IOStage, decode_q: _BoundedQ, seed: int, tracer) -> None:
        self.io = io
        self.decode_q = decode_q
        self.seed = seed
        self.tracer = tracer
        self.peak = seed
        self.widened = 0
        self.narrowed = 0
        self._best = math.inf  # lowest short-window median GET seen
        self._cap = math.inf  # set by a starved GET
        # the current window began at another width (the first: while the
        # stages started), so it is not judged
        self._settling = True
        self._next_t = 0.0  # the current window's earliest end
        self._widths: Deque[Tuple[float, int]] = deque([(0.0, seed)], maxlen=64)
        self._puts = 0
        self._full_puts = 0
        io.get_s = []

    def update(self, ceiling: int) -> None:
        gets = self.io.get_s
        if len(gets) < IO_MIN_GETS:
            return
        now = time.monotonic()
        if now < self._next_t:
            return
        self.io.get_s = []
        median = float(np.median(gets))
        self._next_t = now + IO_WINDOW_GETS * median
        puts, full = self.decode_q.puts, self.decode_q.full_puts
        d_puts, d_full = puts - self._puts, full - self._full_puts
        self._puts, self._full_puts = puts, full
        if self._settling:
            self._settling = False
            return
        self._best = min(self._best, median)
        limit = self.io.gate.limit
        t0 = self.io.oldest_get_t0()
        oldest = 0.0 if t0 is None else now - t0
        p90 = float(np.percentile(gets, 90))
        if oldest > IO_STALL * p90:
            began = self._widths[0][1]
            for t, w in self._widths:
                if t > t0:
                    break
                began = w
            new = min(limit, max(self.seed, int(began / IO_WIDEN)))
            self._cap = min(self._cap, new)
        elif median > IO_LATENCY_TOL * self._best:
            new = max(self.seed, int(limit / IO_WIDEN))
        elif (oldest <= IO_LATENCY_TOL * p90 and self.io.parked()
              and d_full < IO_FULL_SHARE * d_puts):
            new = max(limit, min(ceiling, self._cap,
                                 max(limit + 1, math.ceil(limit * IO_WIDEN))))
        else:
            return
        if new == limit:
            return
        new = self.io.resize(new)
        self._widths.append((now, new))
        self._settling = True
        if new > limit:
            self.peak = max(self.peak, new)
            self.widened += 1
            self.tracer.count(IO_WIDENED)
        else:
            self.narrowed += 1
            self.tracer.count(IO_NARROWED)

    def stats(self) -> Dict[str, int]:
        return {"seed": self.seed, "limit": self.io.gate.limit, "peak": self.peak,
                "widened": self.widened, "narrowed": self.narrowed}


# ---------------------------------------------------------------------------
# CPU stage
# ---------------------------------------------------------------------------


class _CPUStage:
    """decode + augment on a dedicated gated thread pool.

    ``hard_cap`` threads exist; effective parallelism is the gate, so the
    autotuner resizes without thread churn.  The gate is acquired BEFORE
    pulling from the fetch->decode queue — a surplus thread waits empty-
    handed rather than holding a sample hostage behind the gate.

    ``active=False`` parks the stage (threads idle without pulling work):
    the iterator flips it when the ``cpu_executor`` knob swaps the CPU stage
    to the process pool — in-flight samples still finish here, new ones go
    to whichever stage is active, and strict reorder is oblivious to which
    executor produced a sample."""

    def __init__(
        self,
        dataset,
        *,
        width: int,
        hard_cap: int,
        decode_q: _BoundedQ,
        done_q: "queue.Queue",
        stop: threading.Event,
        tracer,
    ) -> None:
        self.dataset = dataset
        self.decode_q = decode_q
        self.done_q = done_q
        self.stop = stop
        self.tracer = tracer
        self.hard_cap = max(width, hard_cap)
        self.gate = AdjustableSemaphore(width)
        self.active = True
        # threads are spawned lazily up to the CURRENT gate width (mirroring
        # ThreadPoolExecutor's lazy growth in the IO stage): a hard_cap of 32
        # must not cost 32 polling threads while the tuned width is 2
        self.threads: List[threading.Thread] = []
        self._spawn_lock = threading.Lock()
        self._ensure_threads(width)

    @property
    def width(self) -> int:
        return self.gate.limit

    def _ensure_threads(self, width: int) -> None:
        with self._spawn_lock:
            while len(self.threads) < min(max(width, 1), self.hard_cap):
                t = threading.Thread(
                    target=self._run, name=f"pipe-cpu-{len(self.threads)}",
                    daemon=True,
                )
                self.threads.append(t)
                t.start()

    def resize(self, width: int) -> int:
        w = max(1, min(int(width), self.hard_cap))
        self.gate.set_limit(w)
        self._ensure_threads(w)
        return w

    def _run(self) -> None:
        while not self.stop.is_set():
            if not self.active:
                time.sleep(0.05)
                continue
            if not self.gate.acquire(timeout=0.1):
                continue
            try:
                try:
                    s: _Sample = self.decode_q.get(timeout=0.1)
                except queue.Empty:
                    continue
                self._process(s)
            finally:
                self.gate.release()

    def _process(self, s: _Sample) -> None:
        try:
            raw, s.raw = s.raw, None
            with self.tracer.span(STAGE_DECODE, index=s.index,
                                  batch_id=s.batch_id):
                decoded = self.dataset.decode_raw(raw, s.index)
            with self.tracer.span(STAGE_AUGMENT, index=s.index,
                                  batch_id=s.batch_id):
                item = self.dataset.augment_item(decoded, s.index)
            self.done_q.put((s, item))
        except BaseException as e:
            self.done_q.put((s, _Failure(e)))

    def join(self, timeout: float = 2.0) -> None:
        for t in self.threads:
            t.join(timeout=timeout)


# ---------------------------------------------------------------------------
# process-backed CPU stage (the GIL escape)
# ---------------------------------------------------------------------------

# attempts per sample across worker crashes: a dead worker fails only its
# in-flight sample, and only after this many fresh workers also died on it
# (then it is almost certainly the sample killing the worker, not bad luck)
PROC_TASK_ATTEMPTS = 3


def _cpu_proc_main(payload: bytes, conn, shm_spec=None) -> None:
    """Spawn entry point for one CPU worker process.

    Runs ONLY ``decode_raw`` + ``augment_item`` on tasks received over the
    pipe; storage IO, assembly and tracing all stay in the parent.  Stage
    endpoints are measured here with ``time.monotonic`` (system-wide
    CLOCK_MONOTONIC) and shipped home so the parent can record real
    per-worker decode/augment spans.  A ``bind`` message replaces the
    dataset wholesale — how the parent pushes per-epoch state (e.g. the
    augmentation epoch) into a pool that outlives iterators.

    ``shm_spec`` = ``(name, slot_bytes, slots)`` attaches the zero-copy
    transport (``PipelineConfig.transport="shm"``): finished samples are
    packed into the parent-owned slab and shipped as ``done_shm`` handles;
    ``free`` returns slots the parent consumed, ``slab_reset`` reclaims
    everything at an epoch takeover, ``slab_cap`` is the autotuner's live
    pressure knob.  Anything that can't pack falls back to the pickle
    ``done`` with the reason attached.  ``die`` is the test-only crash
    injection hook (:meth:`_CPUProcessPool.inject_crash`).  A ``ready``
    message, sent once the dataset is loaded, tells the parent it may send
    tasks."""
    try:
        dataset = pickle.loads(payload)
    except BaseException as e:  # exotic: parent pre-validated pickling
        try:
            conn.send(("crash", f"worker could not unpickle dataset: {e!r}"))
        except OSError:
            pass
        conn.close()
        return
    writer = None
    if shm_spec is not None:
        try:
            writer = shm_mod.SlabWriter(*shm_spec)
        except BaseException as e:
            # segment vanished (parent raced shutdown) — degrade to pipe
            try:
                conn.send(("crash", f"worker could not attach slab: {e!r}"))
            except OSError:
                pass
            writer = None
    try:
        conn.send(("ready",))
    except OSError:
        conn.close()
        return
    die_on_task: Optional[str] = None
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            break
        tag = msg[0]
        if tag == "stop":
            break
        if tag == "bind":
            try:
                dataset = pickle.loads(msg[1])
            except BaseException as e:
                try:
                    conn.send(("crash", f"worker could not rebind dataset: {e!r}"))
                except OSError:
                    pass
                break
            continue
        if tag == "free":
            if writer is not None:
                writer.free_slots(msg[1])
            continue
        if tag == "slab_reset":
            if writer is not None:
                writer.reset()
            continue
        if tag == "slab_cap":
            if writer is not None:
                writer.set_cap(msg[1])
            continue
        if tag == "die":
            # crash injection: "now" dies immediately; "mid_slab_write"
            # dies on the NEXT task with a slot claimed and half-written —
            # the handle is never sent, so the parent must reclaim the slot
            # via slab retirement and retry the sample elsewhere
            if msg[1] == "mid_slab_write" and writer is not None:
                die_on_task = msg[1]
                continue
            os._exit(1)
        _, sid, index, raw = msg
        try:
            t0 = time.monotonic()
            decoded = dataset.decode_raw(raw, index)
            t1 = time.monotonic()
            item = dataset.augment_item(decoded, index)
            t2 = time.monotonic()
            if die_on_task == "mid_slab_write":
                slot = writer._take_slot()
                if slot is not None:
                    writer.shm.buf[slot * writer.slot_bytes] = 0xAB
                os._exit(1)
            if writer is not None:
                handle, why = writer.try_pack(item)
                if handle is not None:
                    conn.send(("done_shm", sid, handle, (t0, t1, t2)))
                    continue
            else:
                why = None
            conn.send(("done", sid, item, (t0, t1, t2), why))
        except BaseException as e:
            try:
                pickle.dumps(e)
                exc: BaseException = e
            except Exception:
                exc = RuntimeError(
                    f"cpu worker failed on sample {index}: {e!r}"
                )
            try:
                conn.send(("err", sid, exc))
            except OSError:
                break
    if writer is not None:
        writer.close()
    conn.close()


# tasks in flight per worker: one EXECUTING plus one QUEUED in its pipe.
# The prefilled task hides the parent round trip (result -> pump wakes ->
# dispatch -> child recv), which on a saturated host costs whole scheduler
# quanta — without it every worker idles that long between samples.
PROC_PREFILL_DEPTH = 2


class _ProcWorker:
    """Parent-side handle: process + duplex pipe + in-flight task ids (FIFO:
    the child answers in send order).  ``send_lock`` serializes writes to
    the pipe: during an epoch takeover the outgoing pump can still be
    mid-``send`` (pipe full behind a slow decode) when ``attach`` broadcasts
    the rebind — unsynchronized interleaved writes would corrupt the pickle
    stream."""

    __slots__ = ("proc", "conn", "sids", "send_lock", "slab", "ready")

    def __init__(self, proc, conn, slab=None) -> None:
        self.proc = proc
        self.conn = conn
        self.sids: List[int] = []  # at most PROC_PREFILL_DEPTH entries
        # set by the child's "ready" message: a task queued on a worker that
        # is still starting waits out its start-up (hundreds of ms) while
        # started workers decode everything after it
        self.ready = False
        self.send_lock = threading.Lock()
        self.slab: Optional[shm_mod.ParentSlab] = slab  # shm transport only

    def send(self, msg: Tuple) -> None:
        with self.send_lock:
            self.conn.send(msg)


def _finalize_pool(slabs: List["shm_mod.ParentSlab"],
                   shutdown: threading.Event) -> None:
    """weakref.finalize target for :class:`_CPUProcessPool` (must not hold
    the pool itself): bar further spawns, then unlink every slab."""
    shutdown.set()
    shm_mod.close_slabs(slabs)


class _CPUProcessPool:
    """Spawn-based decode+augment worker pool, owned by the LOADER.

    Spawning a worker costs hundreds of milliseconds (fresh interpreter +
    numpy import), so unlike the per-epoch thread stages the pool PERSISTS
    across epochs: each epoch's :class:`_ProcCPUStage` attaches to it,
    re-``bind``s the freshly pickled dataset (carrying ``set_epoch`` state),
    and detaches at shutdown without killing workers.  ``owner`` is the
    takeover token — when a new epoch's stage attaches while an abandoned
    iterator's pump thread is still unwinding, the old pump notices it lost
    ownership and exits instead of racing the new one for the pipes.  Task
    ids are pool-global and monotonic, so results from an abandoned epoch's
    tasks are recognized and dropped by the next stage.  Workers are daemon
    processes: an exiting interpreter never hangs on the pool."""

    def __init__(self, payload: bytes, hard_cap: int,
                 shm_spec: Optional[Tuple[int, int]] = None) -> None:
        self.ctx = multiprocessing.get_context("spawn")
        self.payload = payload
        self.hard_cap = max(1, hard_cap)
        self.workers: List[_ProcWorker] = []
        self.owner: Optional[Any] = None
        self.crashes = 0  # workers that died unexpectedly
        self.respawns = 0
        # last child-reported diagnostic ("crash" message): without it, an
        # unpickle/rebind failure in the child surfaces only as a generic
        # "worker died" after the respawn churn burns every retry
        self.last_error: Optional[str] = None
        self._sid = 0
        self._lock = threading.Lock()
        self._closed = False
        # shm transport: (slot_bytes, slots) per worker slab, or None for
        # the pickle pipe.  The parent creates/owns every slab; _slabs is a
        # live list shared with the exit finalizer so segments allocated
        # after respawns are still unlinked if the pool is never closed.
        # The shared _shutdown flag closes a shutdown race: the finalizer
        # runs BEFORE multiprocessing's own atexit terminates the daemon
        # workers, so the (daemon) pump thread may reap those corpses and
        # respawn replacements AFTER the slabs were unlinked — a segment
        # born then has nothing left to clean it up.  ensure() refuses to
        # spawn once the flag is set.
        self.shm_spec = shm_spec
        self.slab_cap: Optional[int] = None  # live usable-slot bound
        self._slabs: List[shm_mod.ParentSlab] = []
        self._shutdown = threading.Event()
        self._finalizer = weakref.finalize(
            self, _finalize_pool, self._slabs, self._shutdown)

    def next_sid(self) -> int:
        with self._lock:
            self._sid += 1
            return self._sid

    def attach(self, stage: Any, payload: bytes) -> None:
        with self._lock:
            self.owner = stage
            rebind = payload != self.payload
            self.payload = payload
        if rebind:
            for w in list(self.workers):  # snapshot: an old pump may mutate
                try:
                    w.send(("bind", payload))
                except OSError:
                    pass  # dead worker; the pump's reap pass replaces it

    def spawn_one(self) -> None:
        parent_conn, child_conn = self.ctx.Pipe()
        slab = None
        worker_spec = None
        if self.shm_spec is not None:
            slab = shm_mod.ParentSlab(*self.shm_spec)
            self._slabs.append(slab)
            worker_spec = slab.spec()
        proc = self.ctx.Process(
            target=_cpu_proc_main,
            args=(self.payload, child_conn, worker_spec),
            name=f"pipe-cpu-proc-{len(self.workers)}",
            daemon=True,
        )
        proc.start()
        child_conn.close()  # the child holds its own copy
        w = _ProcWorker(proc, parent_conn, slab)
        if slab is not None and self.slab_cap is not None:
            # respawned workers must honour the tuned slab-pressure cap too
            try:
                w.send(("slab_cap", self.slab_cap))
            except OSError:  # pragma: no cover - died at birth; reap handles
                pass
        self.workers.append(w)

    def ensure(self, n: int) -> None:
        # under the lock: during an epoch-boundary takeover the outgoing and
        # incoming pump threads briefly coexist, and unsynchronized growth
        # could overshoot hard_cap
        with self._lock:
            if self._closed or self._shutdown.is_set():
                return
            while len(self.workers) < min(max(n, 1), self.hard_cap):
                self.spawn_one()

    def remove(self, w: _ProcWorker) -> None:
        with self._lock:
            if w in self.workers:
                self.workers.remove(w)
        if w.slab is not None:
            # already-delivered views stay valid (parent owns the mapping);
            # the name is dropped now so nothing leaks past the pool
            w.slab.retire()

    def reset_slabs(self) -> None:
        """Epoch takeover: every slot is reclaimed wholesale (a previous
        iterator may have been abandoned with handles it never released)."""
        for w in list(self.workers):
            if w.slab is None:
                continue
            w.slab.reset_accounting()
            try:
                w.send(("slab_reset",))
            except OSError:
                pass  # dead worker; the pump's reap pass replaces it

    def set_slab_cap(self, cap: int) -> None:
        """Autotuner's live slab-pressure knob: bound how many slots each
        worker may use (lower = earlier pickle fallback, less memory hot)."""
        self.slab_cap = cap
        for w in list(self.workers):
            if w.slab is None:
                continue
            try:
                w.send(("slab_cap", cap))
            except OSError:
                pass

    def inject_crash(self, mode: str = "now", worker: int = 0) -> None:
        """TEST HOOK: make worker ``worker`` die — ``"now"`` immediately,
        ``"mid_slab_write"`` on its next task with a slot claimed and
        half-written (exercising crash-safe slot reclamation).  ``worker``
        counts the workers that take tasks; one still starting may get none
        before the epoch ends."""
        with self._lock:
            if not self.workers:
                raise RuntimeError("no workers to crash")
            live = [w for w in self.workers if w.ready] or self.workers
            w = live[worker % len(live)]
        w.send(("die", mode))

    def close(self) -> None:
        """Terminate every worker (loader replacing the pool / tests).
        Epoch-to-epoch shutdown never calls this — stages just detach."""
        self._closed = True
        for w in self.workers:
            try:
                w.send(("stop",))
            except OSError:
                pass
            w.conn.close()
        for w in self.workers:
            w.proc.join(timeout=0.5)
            if w.proc.is_alive():
                w.proc.terminate()
        self.workers.clear()
        shm_mod.close_slabs(self._slabs)
        self._slabs.clear()


class _ProcCPUStage:
    """decode + augment in the spawn-process pool — same contract as
    :class:`_CPUStage` (pull from ``decode_q``, deliver to ``done_q``,
    gate-bounded parallelism, live resize, ``active`` pause flag) with the
    work itself outside the GIL.

    One parent-side pump thread does everything: it claims samples from the
    fetch->decode queue under the :class:`AdjustableSemaphore` gate (a gate
    permit is held from claim to final resolution, so resizes drain exactly
    like the thread stage), assigns up to :data:`PROC_PREFILL_DEPTH` tasks
    per started worker over its pipe (one executing, one queued — the spare
    hides the parent round trip between samples), multiplexes completions with
    ``multiprocessing.connection.wait``, and records the shipped
    decode/augment spans under the worker's pid lane.
    Crash handling: a dead worker's in-flight sample is requeued ahead of
    fresh work and retried on another worker up to ``PROC_TASK_ATTEMPTS``
    total attempts (raw bytes are kept parent-side until success, so a retry
    never refetches), the corpse is reaped and a replacement spawned — one
    crash costs one sample at worst, never the epoch."""

    def __init__(
        self,
        payload: bytes,
        *,
        pool: _CPUProcessPool,
        width: int,
        hard_cap: int,
        decode_q: _BoundedQ,
        done_q: "queue.Queue",
        stop: threading.Event,
        tracer,
    ) -> None:
        self.pool = pool
        self.decode_q = decode_q
        self.done_q = done_q
        self.stop = stop
        self.tracer = tracer
        self.hard_cap = max(width, hard_cap)
        # the gate bounds claimed-but-unresolved samples; it runs at
        # PREFILL_DEPTH x width so every worker can hold a queued spare —
        # `width` stays the stage's parallelism (worker count / knob value)
        self._width = max(1, width)
        self.gate = AdjustableSemaphore(PROC_PREFILL_DEPTH * self._width)
        self.active = True
        self.requeued = 0  # samples retried after a worker crash
        self._boot_deaths = 0  # workers that died before their "ready"
        self._failed = False
        self._inflight: Dict[int, _Sample] = {}
        self._attempts: Dict[int, int] = {}
        self._pending: Deque[int] = deque()  # crash-requeued sids, FIFO
        # transport accounting (stage_stats()["transport"] + bench_shm's
        # bytes-copied claim): pipe samples cost serialize + deserialize
        # (2x payload), shm samples cost the worker's single slab write
        self.shm_samples = 0
        self.pipe_samples = 0
        self.fallbacks: Dict[str, int] = {}
        self.spilled = 0  # shm samples copied out of a nearly full slab
        self.bytes_copied = 0
        pool.attach(self, payload)
        if pool.shm_spec is not None:
            pool.reset_slabs()
        pool.ensure(width)
        self._thread = threading.Thread(
            target=self._run, name="pipe-cpu-pool-pump", daemon=True
        )
        self._thread.start()

    @property
    def width(self) -> int:
        return self._width

    def resize(self, width: int) -> int:
        w = max(1, min(int(width), self.hard_cap))
        self._width = w
        self.gate.set_limit(PROC_PREFILL_DEPTH * w)
        self.pool.ensure(w)
        return w

    # -- pump ---------------------------------------------------------------
    def _owned(self) -> bool:
        return self.pool.owner is self and not self.stop.is_set()

    def _run(self) -> None:
        while self._owned():
            self._reap()
            self.pool.ensure(self._width)
            self._flush_frees()
            self._dispatch()
            workers = list(self.pool.workers)
            busy = [w.conn for w in workers if w.sids or not w.ready]
            if busy:
                for conn in _mp_wait(busy, timeout=0.05):
                    w = next(
                        (x for x in workers if x.conn is conn), None
                    )
                    if w is None:
                        continue
                    try:
                        self._resolve(w, w.conn.recv())
                    except (EOFError, OSError):
                        pass  # worker died mid-send; next reap handles it
            # fully idle case: _dispatch's bounded blocking get is the only
            # wait, so there is nothing further to sleep on here

    def _flush_frees(self) -> None:
        """Return consumed slots to their workers (shm transport): collate
        queued them via ``ShmItem.release``; batching them onto the command
        pipe here keeps the release path lock-only for the consumer."""
        for w in list(self.pool.workers):
            if w.slab is None:
                continue
            pairs = w.slab.drain_freed()
            if not pairs:
                continue
            try:
                w.send(("free", pairs))
            except OSError:
                pass  # dead worker; its slab is retired by the reap pass

    def _dispatch(self) -> None:
        while self._owned():
            # emptiest eligible worker first: fill every idle worker before
            # granting anyone its prefill spare
            candidates = [x for x in list(self.pool.workers)
                          if x.ready and len(x.sids) < PROC_PREFILL_DEPTH
                          and x.proc.is_alive()]
            if not candidates:
                return
            w = min(candidates, key=lambda x: len(x.sids))
            sid: Optional[int] = None
            if self._pending:
                sid = self._pending.popleft()  # retry holds its permit already
            elif self.active and self.gate.acquire(timeout=0):
                any_busy = any(x.sids for x in self.pool.workers)
                try:
                    # bounded blocking get when the whole stage is idle: the
                    # pump's only sleep, released the instant a fetch lands
                    s = self.decode_q.get(timeout=0.0 if any_busy else 0.05)
                except queue.Empty:
                    self.gate.release()
                    return
                sid = self.pool.next_sid()
                self._inflight[sid] = s
                self._attempts[sid] = 1
            else:
                if not self.active and not self._pending:
                    time.sleep(0.02)  # paused: don't spin on the gate
                return
            s = self._inflight[sid]
            w.sids.append(sid)
            try:
                w.send(("task", sid, s.index, s.raw))
            except OSError:
                w.sids.remove(sid)  # broken pipe = dead worker; reap + retry
                self._retry_or_fail(
                    sid, RuntimeError(
                        f"cpu worker pid={w.proc.pid} lost sample {s.index} "
                        "(pipe closed)"
                    ),
                )

    def _reap(self) -> None:
        dead = [w for w in list(self.pool.workers) if not w.proc.is_alive()]
        for w in dead:
            try:
                while w.conn.poll():  # a result may have beaten the crash
                    self._resolve(w, w.conn.recv())
            except (EOFError, OSError):
                pass
            self.pool.crashes += 1
            why = (f"; last worker diagnostic: {self.pool.last_error}"
                   if self.pool.last_error else "")
            for sid in w.sids:  # executing task + any prefilled spare
                self._retry_or_fail(
                    sid,
                    RuntimeError(
                        f"cpu worker pid={w.proc.pid} died "
                        f"(exitcode={w.proc.exitcode}) while decoding{why}"
                    ),
                )
            w.sids.clear()
            w.conn.close()
            self.pool.remove(w)
            self.pool.respawns += 1
            if not w.ready:
                self._boot_deaths += 1
        if (self._boot_deaths >= PROC_TASK_ATTEMPTS and not self._failed
                and not any(x.ready for x in self.pool.workers)):
            # workers die before they can take a task (e.g. the dataset does
            # not unpickle in the child): no sample would ever be retried,
            # so fail the epoch with the child's diagnostic
            self._failed = True
            self.done_q.put((None, _Failure(RuntimeError(
                f"{self._boot_deaths} cpu workers died while starting; last "
                f"worker diagnostic: {self.pool.last_error}"))))

    def _retry_or_fail(self, sid: int, exc: BaseException) -> None:
        s = self._inflight.get(sid)
        if s is None:
            return  # an abandoned epoch's task: nothing to deliver to
        if self._attempts.get(sid, 1) < PROC_TASK_ATTEMPTS:
            self._attempts[sid] = self._attempts.get(sid, 1) + 1
            self.requeued += 1
            self._pending.append(sid)
            return
        del self._inflight[sid]
        self._attempts.pop(sid, None)
        self.done_q.put((s, _Failure(exc)))
        self.gate.release()

    def _resolve(self, w: _ProcWorker, msg: Tuple) -> None:
        tag = msg[0]
        if tag == "ready":
            w.ready = True
            return
        if tag == "crash":
            # the worker is about to exit; reap accounts for it and retries
            # its task (if any).  Keep the child's diagnostic — it is the
            # only evidence of e.g. an unpickle failure inside the worker.
            self.pool.last_error = msg[1]
            return
        sid = msg[1]
        if sid in w.sids:
            w.sids.remove(sid)
        s = self._inflight.pop(sid, None)
        self._attempts.pop(sid, None)
        if s is None:
            return  # stale result from an abandoned epoch's stage
        if tag == "done_shm":
            _, _, handle, (t0, t1, t2) = msg
            item: Any = w.slab.view_item(handle)
            # the worker's slab write is the transport's only copy
            nbytes = handle[2]
            self.shm_samples += 1
            self.bytes_copied += nbytes
            self.tracer.count(BYTES_COPIED, nbytes)
            self._record_proc_spans(w, s, t0, t1, t2)
            s.raw = None
            self.done_q.put((s, item))
        elif tag == "done":
            _, _, item, (t0, t1, t2), why = msg
            # pickle transport: one serialize in the worker, one deserialize
            # here — two full passes over the payload
            nbytes = shm_mod.item_nbytes(item) if isinstance(item, dict) else 0
            self.pipe_samples += 1
            self.bytes_copied += 2 * nbytes
            self.tracer.count(BYTES_COPIED, 2 * nbytes)
            if why is not None:
                self.fallbacks[why] = self.fallbacks.get(why, 0) + 1
            self._record_proc_spans(w, s, t0, t1, t2)
            s.raw = None
            self.done_q.put((s, item))
        else:  # "err": a dataset exception, not a crash — no retry
            self.done_q.put((s, _Failure(msg[2])))
        self.gate.release()

    def _record_proc_spans(self, w: _ProcWorker, s: _Sample,
                           t0: float, t1: float, t2: float) -> None:
        pid = w.proc.pid
        self.tracer.record(STAGE_DECODE, t0, t1, tid=pid,
                           index=s.index, batch_id=s.batch_id, proc=True)
        self.tracer.record(STAGE_AUGMENT, t1, t2, tid=pid,
                           index=s.index, batch_id=s.batch_id, proc=True)

    def join(self, timeout: float = 2.0) -> None:
        self._thread.join(timeout=timeout)


# ---------------------------------------------------------------------------
# assembler / iterator
# ---------------------------------------------------------------------------


class _Group:
    """Window-mode assembly state for up to ``reorder_window`` consecutive
    batches: the group's batch slots are emitted in batch order, each filled
    with the first ``size`` of the group's samples to complete.

    Groups are keyed by dispatch order (a group sequence number), not by
    ``batch_id // window``: each group remembers its own span, so the
    reorder-window knob can change the width live — in-flight groups keep
    the size they were opened with, and only the next group sees the new
    value."""

    __slots__ = ("start_bid", "sizes", "buffer", "indices", "emitted", "closed")

    def __init__(self, start_bid: int) -> None:
        self.start_bid = start_bid  # first dispatched batch_id of the group
        self.sizes: List[int] = []  # batch sizes, in dispatched batch order
        self.buffer: List[Any] = []  # completed items, in completion order
        self.indices: List[int] = []  # dataset indices, completion order
        self.emitted = 0  # batch slots already emitted
        self.closed = False  # a later group was opened: no more batches


class _ShuffleMeter:
    """Windowed shuffle-quality estimator over delivered batch composition.

    Shuffle quality is measured on the *delivered* dataset-index stream
    (what the model actually sees), not the sampler's intent: window-mode
    reassembly fills batches with whichever samples complete first, and
    completion time correlates with content (size, cache state, storage
    locality), silently stratifying batches.  Two normalized [0, 1] numbers:

    * ``within_batch`` — mean normalized Shannon entropy of each batch's
      index histogram over ``buckets`` equal dataset strata.  A uniformly
      shuffled batch draws from every stratum (≈1); a batch stratified by
      completion time concentrates (→0).
    * ``across_batch`` — count-weighted mean, over strata, of the entropy
      of that stratum's distribution across the last ``window_batches``
      batches.  Uniform shuffling spreads each stratum evenly (≈1); epochs
      where a stratum's samples bunch into a few batches score low.

    :meth:`snapshot` is ``stage_stats()["shuffle"]``, what the autotuner's
    entropy floor (``AutotuneConfig.min_shuffle_entropy``) is judged
    against."""

    def __init__(self, dataset_len: int, *, buckets: int = 16,
                 window_batches: int = 32) -> None:
        self.n = max(1, int(dataset_len))
        self.buckets = max(2, min(buckets, self.n))
        self.window_batches = max(2, window_batches)
        self._hists: Deque[np.ndarray] = deque(maxlen=self.window_batches)
        self._within: Deque[float] = deque(maxlen=self.window_batches)
        self.batches = 0

    def note_batch(self, indices) -> None:
        if indices is None or len(indices) == 0:
            return
        idx = np.asarray(indices, dtype=np.int64)
        strata = np.minimum(idx * self.buckets // self.n, self.buckets - 1)
        hist = np.bincount(strata, minlength=self.buckets).astype(np.float64)
        p = hist / hist.sum()
        nz = p[p > 0.0]
        hmax = math.log(min(len(idx), self.buckets))
        within = float(-(nz * np.log(nz)).sum() / hmax) if hmax > 0 else 1.0
        self._within.append(within)
        self._hists.append(hist)
        self.batches += 1

    def snapshot(self) -> Dict[str, Any]:
        if not self._within:
            return {"within_batch": None, "across_batch": None, "batches": 0}
        within = float(np.mean(self._within))
        across = None
        if len(self._hists) >= 2:
            m = np.stack(self._hists)  # (batches, strata)
            totals = m.sum(axis=0)  # per-stratum sample counts
            hmax = math.log(m.shape[0])
            acc = 0.0
            for k in range(m.shape[1]):
                if totals[k] <= 0:
                    continue
                q = m[:, k] / totals[k]
                nz = q[q > 0.0]
                acc += float(totals[k]) * float(-(nz * np.log(nz)).sum() / hmax)
            across = acc / float(totals.sum())
        return {
            "within_batch": round(within, 4),
            "across_batch": round(across, 4) if across is not None else None,
            "batches": self.batches,
        }


class _PipelineIter:
    """Iterator over a :class:`~repro.core.loader.ConcurrentDataLoader` in
    pipeline mode — same external contract as ``_LoaderIter`` (ordered or
    windowed delivery, epoch accounting, autotune ``on_batch`` at the safe
    between-batch boundary, shutdown semantics)."""

    def __init__(self, loader) -> None:
        self.loader = loader
        cfg = loader.cfg
        self.cfg = cfg
        self.tracer = loader.tracer
        at = cfg.autotune
        dataset = loader.dataset
        pipe = cfg.pipeline
        self.split = bool(dataset.supports_split())
        self.strict = pipe.reorder == "strict"
        self.window = 1 if self.strict else max(1, pipe.reorder_window)

        # stage sizing: 0 derives io_workers, seeded at the legacy loader's
        # total fetch-thread count; without an autotuner _IOWidth then sizes
        # the gate from observed GET latency (split datasets only: a
        # monolithic fetch also decodes, so its width stays at the seed)
        io_workers = pipe.io_workers or max(1, cfg.num_workers * cfg.num_fetch_workers)
        cpu_workers = pipe.cpu_workers or 4
        queue_depth = max(1, pipe.stage_queue_depth)
        self.max_outstanding = max(1, cfg.num_workers * cfg.prefetch_factor)
        # knob ceilings widen over the static config (enabling autotune must
        # never cap the loader below its autotune=off operating point)
        self._max_io_bound = max(at.max_fetch_workers, io_workers)
        self._max_cpu_bound = max(at.max_cpu_workers, cpu_workers)
        self._max_queue_bound = max(at.max_stage_queue, queue_depth)
        self._max_outstanding_bound = max(at.max_outstanding, self.max_outstanding)
        if at.enabled:
            # resume from values the controller already learned (prev epoch)
            tuned = loader._tuned
            if not self.strict:
                self.window = min(
                    max(tuned.get("reorder_window", self.window),
                        at.min_reorder_window),
                    max(at.max_reorder_window, self.window),
                )
            io_workers = min(
                max(tuned.get("io_workers", io_workers), at.min_fetch_workers),
                self._max_io_bound,
            )
            cpu_workers = min(
                max(tuned.get("cpu_workers", cpu_workers), at.min_cpu_workers),
                self._max_cpu_bound,
            )
            queue_depth = min(
                max(tuned.get("stage_queue", queue_depth), at.min_stage_queue),
                self._max_queue_bound,
            )
            self.max_outstanding = min(
                max(tuned.get("outstanding", self.max_outstanding),
                    at.min_outstanding),
                self._max_outstanding_bound,
            )

        # budget co-tuning (AutotuneConfig.thread_budget): io and cpu widths
        # are one coupled knob under a fixed total, so normalize the static
        # shape onto the budget here — the split value is the IO width and
        # the CPU stage always gets the remainder
        self._budget = (
            at.thread_budget
            if at.enabled and at.thread_budget > 0 and self.split
            else 0
        )
        if at.enabled and at.thread_budget > 0 and not self.split:
            # monolithic fallback: no CPU stage to trade against, but the
            # budget is still a promise about total width — cap the IO knob
            # at it rather than silently reverting to the unbounded ceiling
            self._max_io_bound = min(self._max_io_bound, at.thread_budget)
            io_workers = min(io_workers, at.thread_budget)
        self._split_lo = self._split_hi = 0
        if self._budget:
            b = self._budget
            self._split_lo = max(at.min_fetch_workers, b - self._max_cpu_bound, 1)
            self._split_hi = max(self._split_lo, b - max(at.min_cpu_workers, 1))
            seed = io_workers
            if pipe.io_workers == 0 and "io_cpu_split" not in loader._tuned:
                # cores-aware split seed: the CPU stage is compute-bound, so
                # start it near the cores this process may actually use
                # (cgroup quota aware) and give IO the budget's remainder —
                # the co-tuner then begins near the optimum instead of at a
                # constant derived from fetch-thread counts
                from repro.core.utilization import available_cpu_count

                seed = b - available_cpu_count()
            io_workers = min(
                max(loader._tuned.get("io_cpu_split", seed),
                    self._split_lo),
                self._split_hi,
            )
            cpu_workers = b - io_workers

        # CPU executor kind: static config, overridden by the tuned value
        # when the budget co-tuner flipped it in a previous epoch
        self.cpu_kind = pipe.cpu_executor if self.split else "thread"
        if at.enabled and self.split and "cpu_executor" in loader._tuned:
            self.cpu_kind = (
                "process" if loader._tuned["cpu_executor"] else "thread"
            )
        # the process stage ships a pickled dataset copy to each spawn
        # worker (decode/augment state only — see MapDataset's picklability
        # contract).  Pickle once, up front: a clear construction-time error
        # beats an opaque one from inside a worker.
        self._proc_payload: Optional[bytes] = None
        if self.split and (
            self.cpu_kind == "process"
            or (self._budget and at.tune_cpu_executor)
        ):
            try:
                self._proc_payload = pickle.dumps(dataset)
            except Exception as e:
                if self.cpu_kind == "process":
                    raise ValueError(
                        "cpu_executor='process' requires a picklable dataset "
                        "(the process CPU stage ships a pickled copy to each "
                        "spawn worker; drop store/tracer members on pickle — "
                        "see MapDataset's picklability contract): "
                        f"pickling failed with {e!r}"
                    ) from e
                self._proc_payload = None  # exec-kind knob just unavailable

        # process-stage result transport: the zero-copy slab ring only means
        # something when a process stage can exist (split + picklable);
        # everything else keeps the pickle pipe (and the thread stage has no
        # transport at all — items never leave the process)
        self.transport = "pipe"
        self._shm_spec: Optional[Tuple[int, int]] = None
        if pipe.transport == "shm" and self._proc_payload is not None:
            self.transport = "shm"
            self._shm_spec = (pipe.slab_slot_bytes, pipe.slab_slots)
        # slab-pressure knob state (usable-slot cap <= allocated slots)
        self._slab_cap = self._shm_spec[1] if self._shm_spec else 0
        if at.enabled and self._shm_spec and "slab_slots" in loader._tuned:
            self._slab_cap = min(
                max(loader._tuned["slab_slots"], at.min_slab_slots),
                self._shm_spec[1],
            )

        self._stop = threading.Event()
        self.decode_q = _BoundedQ(queue_depth, self._stop)
        self.done_q: "queue.Queue" = queue.Queue()
        # sharded delivery: lane threads collate + device-transfer each mesh
        # slice of the batch and push the composed global array back into
        # done_q as a (_Composed, batch) token (repro.core.delivery)
        self._assembler = None
        # pinned host staging (repro.core.staging): only meaningful for the
        # default collate (a custom collate_fn owns its own batch layout)
        from repro.data.dataset import collate as _default_collate

        staging_n = (
            pipe.staging_buffers
            if loader.collate_fn is _default_collate else 0
        )
        if loader.delivery_plan is not None:
            from repro.core.delivery import ShardedAssembler  # lazy: jax

            self._assembler = ShardedAssembler(
                loader.delivery_plan,
                loader.collate_fn,
                done_q=self.done_q,
                stop=self._stop,
                tracer=self.tracer,
                staging_buffers=staging_n,
            )
        self._staging = None
        if staging_n > 0 and self._assembler is None:
            from repro.core.staging import HostBatchPool

            self._staging = HostBatchPool(depth=staging_n, tracer=self.tracer)
        adaptive = pipe.io_workers == 0 and not at.enabled and self.split
        if at.enabled:
            io_cap = self._max_io_bound
        elif adaptive:
            # the executor's hard cap is the outstanding sample window
            # (threads are still created lazily, up to the gate's limit)
            io_cap = self.max_outstanding * cfg.batch_size
        else:
            io_cap = io_workers
        self.io = _IOStage(
            dataset,
            mode="asyncio" if cfg.impl == "asyncio" else "threaded",
            width=io_workers,
            hard_cap=io_cap,
            split=self.split,
            decode_q=self.decode_q,
            done_q=self.done_q,
            stop=self._stop,
            tracer=self.tracer,
            hedge=loader.hedge,
        )
        self._io_width = (
            _IOWidth(self.io, self.decode_q, io_workers, self.tracer)
            if adaptive else None
        )
        cpu_hard = self._max_cpu_bound if at.enabled else cpu_workers
        if not self.split:
            # monolithic fallback: the fetch stage already produces finished
            # items, so the CPU stage processes nothing — don't spin up an
            # idle thread pool (much less a process pool) for it
            cpu_workers = cpu_hard = 1
        self._cpu_hard = cpu_hard
        self._cpu_width = cpu_workers
        # both CPU stage kinds share decode_q/done_q and are created lazily;
        # the inactive one (if ever created) is paused, so the cpu_executor
        # knob can swap kinds mid-epoch without disturbing in-flight samples
        self._thread_cpu: Optional[_CPUStage] = None
        self._proc_cpu: Optional[_ProcCPUStage] = None
        self.cpu = self._make_cpu_stage(self.cpu_kind)

        self._sampler_iter = iter(loader.sampler)
        self._exhausted = False
        self._shutdown = False
        self._lock = threading.Lock()
        self._dispatched_samples = 0
        self._completed_samples = 0
        self._dispatched_batches = 0
        self._emitted_batches = 0
        self._bid_base = 0  # first dispatched batch_id (resume offsets it)
        self._max_bid = -1  # highest dispatched batch_id (group closure)
        # samples per batch, learned from the first dispatched task: sharded
        # batches hold batch_size/num_hosts indices, so sizing the window
        # from cfg.batch_size would admit num_hosts x more batches than the
        # legacy loader's prefetch window
        self._per_batch: Optional[int] = None
        # strict-mode assembly: per-batch positional slots + ready buffer
        self._slots: Dict[int, List[Any]] = {}
        self._remaining: Dict[int, int] = {}
        self._ready: Dict[int, Any] = {}
        self._next_bid: Optional[int] = None
        # window-mode assembly: per-group first-N-ready composition, keyed
        # by dispatch-order group sequence number (live-resizable window)
        self._groups: Dict[int, _Group] = {}
        self._cur_group = 0  # next group to deliver
        self._next_gid = 0  # next group to open
        self._gid_of_bid: Dict[int, int] = {}
        self._group_consumed = 0  # absolute bid past the last emitted group
        # shuffle-quality estimator over the delivered index stream (the
        # evidence behind stage_stats()["shuffle"] and the autotuner's
        # reorder-window entropy floor)
        self._shuffle = _ShuffleMeter(loader.sampler.dataset_len)
        # strict/sharded batch composition equals the sampler's dispatch —
        # remember it so delivery can be scored without re-deriving indices
        self._batch_indices: Dict[int, Tuple[int, ...]] = {}

        if loader.autotuner is not None:
            from repro.core.autotune import (
                build_budget_knobs,
                build_pipeline_knobs,
                make_weak_knob_callbacks,
            )

            # knob callbacks reach this iterator through a weakref (see
            # make_weak_knob_callbacks): the autotuner outlives every
            # epoch's iterator, and a strong closure would pin an abandoned
            # iterator (and its stage threads) until the next bind().
            _wget, _wset = make_weak_knob_callbacks(self)
            # slab-pressure knob only when the shm transport is live (the
            # slab allocation caps how far the controller may raise it)
            extra_kw: Dict[str, Any] = {}
            if self._shm_spec is not None:
                extra_kw = dict(
                    get_slab=_wget(lambda it: it._slab_cap),
                    set_slab=_wset(lambda it, n: it._set_slab_slots(n)),
                    max_slab=self._shm_spec[1],
                )
            # reorder-window knob only where the window exists: window-mode
            # host delivery (sharded delivery requires strict reorder)
            if not self.strict and self._assembler is None:
                extra_kw.update(
                    get_reorder=_wget(lambda it: it.window),
                    set_reorder=_wset(lambda it, n: it._set_reorder_window(n)),
                )
            if self._budget:
                # budget co-tuning: ONE coupled io/cpu split knob (+ the
                # executor kind when the dataset is process-capable) instead
                # of two independent width knobs
                proc_ok = self._proc_payload is not None
                knobs = build_budget_knobs(
                    at,
                    budget=self._budget,
                    lo_split=self._split_lo,
                    hi_split=self._split_hi,
                    get_split=_wget(lambda it: it.io.gate.limit),
                    set_split=_wset(lambda it, n: it._set_split(n)),
                    get_outstanding=_wget(lambda it: it.max_outstanding),
                    set_outstanding=_wset(lambda it, n: it._set_outstanding(n)),
                    get_queue=_wget(lambda it: it.decode_q.depth),
                    set_queue=_wset(lambda it, n: it._set_stage_queue(n)),
                    get_cpu_executor=(
                        _wget(lambda it: int(it.cpu_kind == "process"))
                        if proc_ok else None
                    ),
                    set_cpu_executor=(
                        _wset(lambda it, n: it._set_cpu_executor(n))
                        if proc_ok else None
                    ),
                    hedge=loader.hedge,
                    max_outstanding=self._max_outstanding_bound,
                    max_queue=self._max_queue_bound,
                    **extra_kw,
                )
            else:
                knobs = build_pipeline_knobs(
                    at,
                    get_io=_wget(lambda it: it.io.gate.limit),
                    set_io=_wset(lambda it, n: it._set_io_workers(n)),
                    get_cpu=_wget(lambda it: it.cpu.width),
                    set_cpu=_wset(lambda it, n: it._set_cpu_workers(n)),
                    get_outstanding=_wget(lambda it: it.max_outstanding),
                    set_outstanding=_wset(lambda it, n: it._set_outstanding(n)),
                    get_queue=_wget(lambda it: it.decode_q.depth),
                    set_queue=_wset(lambda it, n: it._set_stage_queue(n)),
                    hedge=loader.hedge,
                    max_io=self._max_io_bound,
                    max_cpu=self._max_cpu_bound,
                    max_outstanding=self._max_outstanding_bound,
                    max_queue=self._max_queue_bound,
                    **extra_kw,
                )
                if not self.split:
                    # nothing flows through the CPU stage or its queue —
                    # inert knobs would waste the controller's probe windows
                    knobs = [k for k in knobs
                             if k.name not in ("cpu_workers", "stage_queue")]
            loader.autotuner.bind(knobs)
            for knob in loader._cache_knobs:
                loader.autotuner.attach_knob(knob)

        self._pump()

    # -- CPU stage factory / executor swap -----------------------------------
    def _make_cpu_stage(self, kind: str):
        """Create (or reactivate) the CPU stage of the requested kind.  Both
        kinds share decode_q/done_q/stop; the process kind attaches to the
        loader-persistent :class:`_CPUProcessPool` (spawn cost is paid once,
        not per epoch) and rebinding ships this epoch's dataset state."""
        if kind == "process":
            if self._proc_cpu is None:
                pool = self.loader._cpu_pool
                if (pool is None or pool.hard_cap < self._cpu_hard
                        or pool._closed or pool.shm_spec != self._shm_spec):
                    if pool is not None:
                        pool.close()
                    pool = _CPUProcessPool(self._proc_payload, self._cpu_hard,
                                           shm_spec=self._shm_spec)
                    self.loader._cpu_pool = pool
                if self._shm_spec and self._slab_cap < self._shm_spec[1]:
                    pool.set_slab_cap(self._slab_cap)
                self._proc_cpu = _ProcCPUStage(
                    self._proc_payload,
                    pool=pool,
                    width=self._cpu_width,
                    hard_cap=self._cpu_hard,
                    decode_q=self.decode_q,
                    done_q=self.done_q,
                    stop=self._stop,
                    tracer=self.tracer,
                )
            else:
                self._proc_cpu.active = True
                self._proc_cpu.resize(self._cpu_width)
            return self._proc_cpu
        if self._thread_cpu is None:
            self._thread_cpu = _CPUStage(
                self.loader.dataset,
                width=self._cpu_width,
                hard_cap=self._cpu_hard,
                decode_q=self.decode_q,
                done_q=self.done_q,
                stop=self._stop,
                tracer=self.tracer,
            )
        else:
            self._thread_cpu.active = True
            self._thread_cpu.resize(self._cpu_width)
        return self._thread_cpu

    # -- autotuner control surfaces (applied between batches) ----------------
    def _set_io_workers(self, n: int) -> int:
        n = max(self.cfg.autotune.min_fetch_workers, int(n))
        applied = self.io.resize(n)
        self.loader._tuned["io_workers"] = applied
        return applied

    def _resize_cpu(self, n: int) -> int:
        applied = self.cpu.resize(n)
        self._cpu_width = applied
        return applied

    def _set_cpu_workers(self, n: int) -> int:
        n = max(self.cfg.autotune.min_cpu_workers, int(n))
        applied = self._resize_cpu(n)
        self.loader._tuned["cpu_workers"] = applied
        return applied

    def _set_split(self, n: int) -> int:
        """Apply one value of the coupled io/cpu split (budget mode): IO gets
        ``n``, the CPU stage gets ``budget - n``.  The shrinking side is
        resized first so the LIMITS never sum above the budget, even
        transiently (surplus in-flight work drains through its gate)."""
        n = max(self._split_lo, min(int(n), self._split_hi))
        cpu = self._budget - n
        if n >= self.io.gate.limit:
            self._resize_cpu(cpu)
            self.io.resize(n)
        else:
            self.io.resize(n)
            self._resize_cpu(cpu)
        self.loader._tuned["io_cpu_split"] = n
        return n

    def _set_cpu_executor(self, v: int) -> int:
        """Swap the CPU stage kind live (binary budget-mode knob).  The old
        stage is paused, not torn down: its in-flight samples finish into
        the shared done_q (strict reorder is executor-oblivious), and a
        revert two windows later reactivates it for free."""
        want = "process" if int(v) >= 1 else "thread"
        cur = int(self.cpu_kind == "process")
        if want == self.cpu_kind:
            return cur
        if want == "process" and self._proc_payload is None:
            return cur  # not process-capable: echo so the controller skips
        old = self.cpu
        self.cpu = self._make_cpu_stage(want)
        old.active = False
        self.cpu_kind = want
        applied = int(want == "process")
        self.loader._tuned["cpu_executor"] = applied
        return applied

    def _set_outstanding(self, n: int) -> int:
        at = self.cfg.autotune
        n = max(at.min_outstanding, min(int(n), self._max_outstanding_bound))
        self.max_outstanding = n
        self.loader._tuned["outstanding"] = n
        return n

    def _set_stage_queue(self, n: int) -> int:
        n = max(self.cfg.autotune.min_stage_queue, int(n))
        applied = self.decode_q.resize(n, self._max_queue_bound)
        self.loader._tuned["stage_queue"] = applied
        return applied

    def _set_slab_slots(self, n: int) -> int:
        """Slab-pressure knob (shm transport): cap the usable slots per
        worker slab.  Allocation is fixed at construction (slab_slots), so
        the cap only gates which slots the worker may hand out — lowering
        it never touches in-flight slots, it just forces earlier pickle
        fallback; raising it re-admits parked slots on their next free."""
        at = self.cfg.autotune
        hi = self._shm_spec[1] if self._shm_spec else 1
        n = max(at.min_slab_slots, min(int(n), hi))
        self._slab_cap = n
        stage = self._proc_cpu
        if stage is not None:
            stage.pool.set_slab_cap(n)
        self.loader._tuned["slab_slots"] = n
        return n

    def _set_reorder_window(self, n: int) -> int:
        """Reorder-window knob (window mode only): takes effect for the NEXT
        opened group — groups are keyed by dispatch order and remember their
        own span, so in-flight groups keep the size they were opened with
        and the assembly math never sees a mixed window."""
        if self.strict:
            return 1
        at = self.cfg.autotune
        n = max(at.min_reorder_window,
                min(int(n), max(at.max_reorder_window, 1)))
        self.window = n
        self.loader._tuned["reorder_window"] = n
        return n

    # -- dispatch ------------------------------------------------------------
    def _pump(self) -> None:
        """Flatten sampler batches into sample tasks while the in-flight
        sample window has room (the batch-level ``outstanding`` knob times
        the actual per-batch sample count, matching the legacy prefetch
        window even when host sharding shrinks each batch's index list)."""
        if self._exhausted:
            return
        while (
            self._per_batch is None  # first batch sizes the window
            or self._dispatched_samples - self._completed_samples
            < self.max_outstanding * self._per_batch
        ):
            try:
                task: BatchIndices = next(self._sampler_iter)
            except StopIteration:
                self._exhausted = True
                return
            if self._per_batch is None:
                self._per_batch = max(len(task.indices), 1)
            if self._next_bid is None:
                self._next_bid = task.batch_id
                self._bid_base = task.batch_id
                self._group_consumed = task.batch_id
            self._max_bid = max(self._max_bid, task.batch_id)
            n = len(task.indices)
            if self._assembler is not None:
                self._assembler.begin_batch(task.batch_id, n)
                self._batch_indices[task.batch_id] = tuple(task.indices)
            elif self.strict:
                self._slots[task.batch_id] = [None] * n
                self._remaining[task.batch_id] = n
                self._batch_indices[task.batch_id] = tuple(task.indices)
            else:
                gid = self._next_gid - 1
                g = self._groups.get(gid)
                if g is None or g.closed or len(g.sizes) >= self.window:
                    if g is not None:
                        g.closed = True
                    gid = self._next_gid
                    self._next_gid += 1
                    g = _Group(task.batch_id)
                    self._groups[gid] = g
                g.sizes.append(n)
                self._gid_of_bid[task.batch_id] = gid
            self._dispatched_batches += 1
            self._dispatched_samples += n
            for pos, index in enumerate(task.indices):
                self.io.submit(_Sample(task.batch_id, pos, index))

    # -- assembly ------------------------------------------------------------
    def _spill(self, s: _Sample, item: Any) -> Any:
        """shm transport: a sample that the next emit will not collate gives
        its slot back once its worker's slab is nearly full.  Its values are
        copied out (one counted copy), so the slots go to the samples the
        consumer waits on instead of those falling back to the pickle pipe.
        Without this, one slow fetch lets decode run ahead by as many
        samples as complete behind it, each holding a slot."""
        if not isinstance(item, shm_mod.ShmItem):
            return item
        if self.strict:
            head = s.batch_id == self._next_bid
        else:
            head = self._gid_of_bid.get(s.batch_id) == self._cur_group
        if head or item.slab_in_use() + PROC_PREFILL_DEPTH < self._slab_cap:
            return item
        out = item.detach()
        nbytes = shm_mod.item_nbytes(out)
        self._proc_cpu.spilled += 1
        self._proc_cpu.bytes_copied += nbytes
        self.tracer.count(BYTES_COPIED, nbytes)
        return out

    def _absorb(self, s: _Sample, item: Any) -> None:
        self._completed_samples += 1
        if self._assembler is not None:
            # lane routing: the assembler hands the sample to its lane's
            # collate/h2d thread; the composed batch comes back through
            # done_q as a _Composed token, landing in _ready below
            self._assembler.add(s.batch_id, s.pos, item)
            return
        item = self._spill(s, item)
        if self.strict:
            slots = self._slots[s.batch_id]
            slots[s.pos] = item
            self._remaining[s.batch_id] -= 1
            if self._remaining[s.batch_id] == 0:
                del self._remaining[s.batch_id]
                self._ready[s.batch_id] = self._slots.pop(s.batch_id)
        else:
            g = self._groups[self._gid_of_bid[s.batch_id]]
            g.buffer.append(item)
            g.indices.append(s.index)

    def _pop_ready(self) -> Optional[List[Any]]:
        """Return the next deliverable batch's items, or None."""
        if self.strict:
            if self._next_bid is not None and self._next_bid in self._ready:
                items = self._ready.pop(self._next_bid)
                self._shuffle.note_batch(
                    self._batch_indices.pop(self._next_bid, ()))
                self._next_bid += 1
                return items
            return None
        g = self._groups.get(self._cur_group)
        if g is None:
            return None
        if g.emitted < len(g.sizes):
            need = g.sizes[g.emitted]
            if len(g.buffer) >= need:
                items, g.buffer = g.buffer[:need], g.buffer[need:]
                idxs, g.indices = g.indices[:need], g.indices[need:]
                g.emitted += 1
                self._shuffle.note_batch(idxs)
                if g.emitted == len(g.sizes) and (g.closed or self._exhausted):
                    # last slot of a finished group: the consumer cursor may
                    # advance past it (resume replays partial groups only)
                    self._group_consumed = g.start_bid + len(g.sizes)
                return items
            return None
        # every dispatched slot of this group emitted; the group is complete
        # once a later group was opened (dispatch is in batch-id order) or
        # the sampler is exhausted — then advance
        if (g.closed or self._exhausted) and not g.buffer:
            self._group_consumed = g.start_bid + len(g.sizes)
            for bid in range(g.start_bid, g.start_bid + len(g.sizes)):
                self._gid_of_bid.pop(bid, None)
            del self._groups[self._cur_group]
            self._cur_group += 1
            return self._pop_ready()
        return None

    def _emit(self, items: List[Any]) -> Any:
        if self._assembler is not None:
            # sharded delivery: the lane threads already collated and
            # device-transferred every shard — `items` IS the composed,
            # device-resident global batch
            batch = items
        else:
            # absolute batch id, same coordinate space as the per-sample
            # stage spans (which carry the sampler's batch_id) — joinable
            # after resume
            with self.tracer.span(
                STAGE_COLLATE, batch_id=self._bid_base + self._emitted_batches
            ):
                if self._staging is not None:
                    batch = self._staging.collate(items)
                else:
                    batch = self.loader.collate_fn(items)
            # collate is one full pass over the batch either way (np.stack
            # allocates+copies; staging copies into a reused buffer)
            if isinstance(batch, dict):
                self.tracer.count(BYTES_COPIED, shm_mod.item_nbytes(batch))
            # collate copied every view out — hand shm slots back for reuse
            shm_mod.release_items(items)
        self._emitted_batches += 1
        # consumer cursor in absolute batch ids (resume starts past 0), same
        # contract as the legacy iterator's _next_bid bookkeeping
        consumed = self._bid_base + self._emitted_batches
        if not self.strict:
            # a windowed batch holds first-N-ready samples from its whole
            # group, so a mid-group cursor would resume with some samples
            # dropped and others duplicated; hold the cursor at the last
            # fully emitted group's end (maintained in _pop_ready) — a
            # restart replays the partial group, which is the legacy
            # "prefetched-but-unconsumed batches are replayed" contract,
            # and no sample is ever lost
            consumed = max(self._group_consumed, self._bid_base)
        self.loader._consumed = consumed
        return batch

    # -- iteration -----------------------------------------------------------
    def __iter__(self) -> "_PipelineIter":
        return self

    def __next__(self) -> Any:
        from repro.core.loader import deliver_traced  # here to avoid a cycle

        return deliver_traced(self)

    def _next_impl(self) -> Any:
        if self._shutdown:
            raise StopIteration
        from repro.core.loader import LoaderTimeout  # here to avoid a cycle

        deadline = time.monotonic() + self.cfg.timeout_s
        while True:
            if self._io_width is not None:
                self._io_width.update(self.max_outstanding * (self._per_batch or 1))
            items = self._pop_ready()
            if items is not None:
                self._pump()
                return self._emit(items)
            if (
                self._exhausted
                and self._completed_samples >= self._dispatched_samples
                and self._emitted_batches >= self._dispatched_batches
            ):
                self._finish_epoch()
                raise StopIteration
            self._pump()
            self.io.hedge_scan()
            try:
                s, payload = self.done_q.get(timeout=0.1)
            except queue.Empty:
                if time.monotonic() > deadline:
                    self.shutdown()
                    raise LoaderTimeout(
                        f"no sample within {self.cfg.timeout_s}s (dispatched="
                        f"{self._dispatched_samples}, "
                        f"completed={self._completed_samples})"
                    )
                continue
            if isinstance(payload, _Failure):
                self.shutdown()
                raise payload.exc
            if isinstance(s, _Composed):
                # a lane assembler finished a global batch out of band; park
                # it for the strict in-order pop above
                self._ready[s.batch_id] = payload
                continue
            self._absorb(s, payload)

    def _finish_epoch(self) -> None:
        self.shutdown()
        self.loader._note_epoch_end()

    # -- observability -------------------------------------------------------
    def stage_stats(self) -> Dict[str, Any]:
        """Live per-stage snapshot: executor widths, queue occupancy, flow
        counters — the queue numbers are what identify the bottleneck stage
        (and what bench_pipeline asserts overlap with)."""
        out: Dict[str, Any] = {
            "io_workers": self.io.gate.limit,
            "cpu_workers": self.cpu.width,
            "cpu_executor": self.cpu_kind,
            "outstanding_batches": self.max_outstanding,
            "decode_queue": self.decode_q.occupancy(),
            "done_queue": self.done_q.qsize(),
            "in_flight_samples": self._dispatched_samples - self._completed_samples,
            "emitted_batches": self._emitted_batches,
            "split": self.split,
            "reorder": "strict" if self.strict else f"window={self.window}",
            # delivered-stream shuffle quality (see _ShuffleMeter): the
            # within_batch value feeds the autotuner's reorder-window
            # entropy floor via the loader's entropy_fn
            "shuffle": self._shuffle.snapshot(),
        }
        if self._io_width is not None:
            out["io_width"] = self._io_width.stats()
        if self._budget:
            out["thread_budget"] = self._budget
        if self._staging is not None:
            out["staging"] = self._staging.stats()
        if self._proc_cpu is not None:
            pool = self._proc_cpu.pool
            out["cpu_pool"] = {
                "workers": len(pool.workers),
                "crashes": pool.crashes,
                "respawns": pool.respawns,
                "requeued": self._proc_cpu.requeued,
            }
            if pool.last_error:
                out["cpu_pool"]["last_error"] = pool.last_error
            stage = self._proc_cpu
            samples = stage.shm_samples + stage.pipe_samples
            tr: Dict[str, Any] = {
                "kind": self.transport,
                "shm_samples": stage.shm_samples,
                "pipe_samples": stage.pipe_samples,
                "fallbacks": dict(stage.fallbacks),
                "fallback_rate": (
                    round(sum(stage.fallbacks.values()) / samples, 4)
                    if samples else 0.0
                ),
                "spilled": stage.spilled,
                "bytes_copied": stage.bytes_copied,
            }
            if pool.shm_spec is not None:
                slot_bytes, slots = pool.shm_spec
                live = [w.slab for w in pool.workers if w.slab is not None]
                in_use = sum(s.in_use for s in live)
                peak = max((s.peak for s in live), default=0)
                total = slots * max(len(live), 1)
                tr.update(
                    slot_bytes=slot_bytes,
                    slab_slots=slots,
                    slab_cap=self._slab_cap,
                    slots_in_use=in_use,
                    slots_peak_per_worker=peak,
                    occupancy=round(in_use / total, 4) if total else 0.0,
                )
            out["transport"] = tr
        hedge = self.io.hedge
        if hedge is not None:
            out["hedges_issued"] = hedge.hedges_issued
            out["hedges_won"] = hedge.hedges_won
        if self._assembler is not None:
            # per-lane composed counts / collate / h2d means — the lane-skew
            # signal autotune and bench_sharded read
            out["delivery"] = self._assembler.stats()
        return out

    # -- shutdown ------------------------------------------------------------
    def shutdown(self) -> None:
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
        # final snapshot for post-epoch observability: the loader holds this
        # iterator only weakly (so threads are never pinned), but callers
        # still want stage_stats() after the epoch ends
        try:
            self.loader._last_stage_stats = self.stage_stats()
        except Exception:  # pragma: no cover - stats must never block exit
            pass
        self._stop.set()
        if self._assembler is not None:
            self._assembler.close()
        self.io.close()
        # join every CPU stage ever created this epoch (an executor-kind
        # flip leaves the paused one alive); the process POOL persists on
        # the loader — only the pump thread belongs to this iterator
        for stage in (self._thread_cpu, self._proc_cpu):
            if stage is not None:
                stage.join()

    def __del__(self) -> None:  # pragma: no cover - best effort
        try:
            self.shutdown()
        except Exception:
            pass
