"""Accelerator busy/idle accounting — the Table-3 columns.

The paper samples ``nvidia-smi`` at 10 Hz in a sidecar.  On TPU/CPU we derive
the same statistics from the step-execution spans: a 100 ms window is "busy"
by the fraction of it covered by ``run_training_batch`` spans.

* ``util_zero_pct``  — % of windows with zero coverage  (GPU_util=0)
* ``util_pos_avg``   — mean coverage % over non-zero windows (GPU_util>0)
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Sequence

from repro.core.tracing import RUN_TRAINING_BATCH, Span, Tracer, union_duration


@dataclass
class UtilStats:
    util_zero_pct: float
    util_pos_avg: float
    busy_fraction: float
    wall_s: float


def _coverage(spans: Sequence[Span], w0: float, w1: float) -> float:
    cov = 0.0
    for s in spans:
        lo, hi = max(s.t0, w0), min(s.t1, w1)
        if hi > lo:
            cov += hi - lo
    return min(cov / (w1 - w0), 1.0)


def sample_utilization(
    spans: Sequence[Span], t0: float, t1: float, hz: float = 10.0
) -> UtilStats:
    wall = max(t1 - t0, 1e-9)
    dt = 1.0 / hz
    n = max(int(wall / dt), 1)
    # bucket spans for O(n + m) overlap queries
    zero = 0
    pos: List[float] = []
    spans = sorted(spans, key=lambda s: s.t0)
    j0 = 0
    for w in range(n):
        w0 = t0 + w * dt
        w1 = min(w0 + dt, t1)
        # advance start pointer past spans that ended before this window
        while j0 < len(spans) and spans[j0].t1 < w0:
            j0 += 1
        j = j0
        window_spans = []
        while j < len(spans) and spans[j].t0 < w1:
            window_spans.append(spans[j])
            j += 1
        c = _coverage(window_spans, w0, w1)
        if c <= 0.0:
            zero += 1
        else:
            pos.append(c)
    busy = union_duration(list(spans)) / wall
    return UtilStats(
        util_zero_pct=100.0 * zero / n,
        util_pos_avg=100.0 * (sum(pos) / len(pos) if pos else 0.0),
        busy_fraction=busy,
        wall_s=wall,
    )


def accelerator_stats(tracer: Tracer, t0: float, t1: float, hz: float = 10.0) -> UtilStats:
    return sample_utilization(tracer.spans(RUN_TRAINING_BATCH), t0, t1, hz)


def recent_busy_fraction(
    tracer: Tracer, window_s: float = 2.0, now: Optional[float] = None
) -> Optional[float]:
    """Accelerator busy fraction over the trailing window — the live signal
    the autotuner's utilization gate consumes (``AutotuneConfig.util_gate``).

    The window is anchored at the END of the last *completed* training-step
    span, not at the wall clock: only completed spans are recorded, so a
    now-anchored window read mid-step would count the in-flight step's time
    as idle and systematically under-report utilization whenever the step
    duration approaches ``window_s`` (the long-step regime the gate most
    targets).

    Returns ``None`` when there is no usable signal — no step span in recent
    history, the last step completed too long ago (training paused, or an
    in-flight step much longer than the window), or a saturated ``Tracer``
    dropping spans.  No signal, no gate: failing open beats tuning against a
    stale reading."""
    t_now = time.monotonic() if now is None else now
    recent = tracer.recent_spans(RUN_TRAINING_BATCH, t_now - 3 * window_s)
    if not recent:
        return None
    anchor = max(s.t1 for s in recent)
    if t_now - anchor > 2 * window_s:
        return None  # stale: paused, or an in-flight step we can't see
    t1, t0 = anchor, anchor - window_s
    spans = [s for s in recent if s.t1 > t0 and s.t0 < t1]
    clipped = [Span(s.name, max(s.t0, t0), min(s.t1, t1), s.tid) for s in spans]
    return min(union_duration(clipped) / max(window_s, 1e-9), 1.0)
