"""From a profiler trace to device numbers.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
compact dict: per device, the operations (``XLA Ops``) and the program
executions (``XLA Modules``) as ``[name, start_ns, duration_ns]``, plus the
benchmark's own host markers (``bench:*`` annotations). Everything else here
works on that dict, so the reduction is checked against a small recorded
trace kept with the tests.

Clocks: the trace has a clock of its own. The harness puts a marker on it
at the start and at the end of the window, and records the same moments on
the host's monotonic clock, on which the program's spans are taken; the
markers give the offset between the two.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out: Dict = {"devices": {}, "marks": []}
    for plane in pd.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)$", plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
            out["devices"][m.group(1)] = dev
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                out["marks"] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events if e.name.startswith("bench:")]
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Sequence[Interval], w0: float, w1: float) -> List[Interval]:
    return [(max(a, w0), min(b, w1)) for a, b in intervals if b > w0 and a < w1]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


class DeviceTrace:
    """The trace of one window, in seconds on the host's monotonic clock."""

    def __init__(self, data: Dict, mono_start: float, mono_end: float) -> None:
        marks = {name: start for name, start, _ in data["marks"]}
        if "bench:window_start" not in marks or "bench:window_end" not in marks:
            raise ValueError("the trace lacks the benchmark's window markers")
        t0, t1 = marks["bench:window_start"], marks["bench:window_end"]
        # one linear map from trace ns to monotonic seconds, fixed by the two
        # markers (it absorbs any rate difference between the clocks)
        scale = (mono_end - mono_start) / max(t1 - t0, 1.0)
        self.to_mono = lambda ns: mono_start + (ns - t0) * scale
        self.window = (mono_start, mono_end)
        self.devices = {}
        for dev, d in data["devices"].items():
            self.devices[dev] = {
                key: [(n, self.to_mono(s), self.to_mono(s + dur)) for n, s, dur in d.get(key, [])]
                for key in ("ops", "modules")
            }

    @property
    def count(self) -> int:
        return len(self.devices)

    def _ops(self, dev) -> List[Interval]:
        return clip([(a, b) for _, a, b in self.devices[dev]["ops"]], *self.window)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(total(union(self._ops(d))) for d in self.devices) / len(self.devices)

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def module_runs(self, pattern: str, dev=None) -> List[Tuple[float, float]]:
        """Executions of the programs whose name matches ``pattern``."""
        dev = next(iter(self.devices)) if dev is None else dev
        rx = re.compile(pattern)
        return clip([(a, b) for n, a, b in self.devices[dev]["modules"] if rx.search(n)],
                    *self.window)

    def op_time_in(self, runs: Sequence[Interval], dev=None, pattern: str = "") -> float:
        """Union of the time of operations (matching ``pattern``) that lie
        inside ``runs``."""
        dev = next(iter(self.devices)) if dev is None else dev
        rx = re.compile(pattern)
        ops = [(a, b) for n, a, b in self.devices[dev]["ops"] if not pattern or rx.search(n)]
        return total(intersect(union(clip(ops, *self.window)), union(runs)))

    def op_time(self, pattern: str) -> Dict[str, float]:
        """Per device, the union of the time of operations matching ``pattern``."""
        rx = re.compile(pattern)
        return {d: total(union(clip([(a, b) for n, a, b in v["ops"] if rx.search(n)],
                                    *self.window)))
                for d, v in self.devices.items()}

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` operations that took most device time, summed over
        their executions and averaged over the devices."""
        acc: Dict[str, float] = {}
        for v in self.devices.values():
            for n, a, b in v["ops"]:
                n = n.split(" = ")[0].lstrip("%")  # the HLO instruction's name
                for x, y in clip([(a, b)], *self.window):
                    acc[n] = acc.get(n, 0.0) + (y - x)
        n_dev = max(len(self.devices), 1)
        return [[n, t / n_dev] for n, t in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, dev=None) -> List[Interval]:
        """The intervals of the window in which no operation ran on ``dev``."""
        dev = next(iter(self.devices)) if dev is None else dev
        return gaps_between(self._ops(dev), *self.window)


def gaps_between(intervals: Sequence[Interval], w0: float, w1: float) -> List[Interval]:
    """The parts of [w0, w1] that ``intervals`` leave uncovered."""
    out, cur = [], w0
    for a, b in union(clip(intervals, w0, w1)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < w1:
        out.append((cur, w1))
    return out


def name_gap(gap: Interval, host: Dict[str, List[Interval]]) -> str:
    """What the host was doing in ``gap``: the host span kinds that cover
    most of it (``host`` maps a kind to its intervals), else ``host_other``."""
    length = max(gap[1] - gap[0], 1e-12)
    shares = [(total(union(clip(ivs, *gap))) / length, kind) for kind, ivs in host.items()]
    named = [kind for share, kind in sorted(shares, reverse=True) if share >= 0.5]
    return "+".join(named) if named else "host_other"


def breakdown(trace: DeviceTrace, host: Dict[str, List[Interval]], k: int = 10) -> Dict:
    gaps = sorted(trace.idle_gaps(), key=lambda g: g[0] - g[1])[:k]
    return {
        "device_ops": trace.top_ops(k),
        "idle_gaps": [[name_gap(g, host), g[1] - g[0]] for g in gaps],
    }


def load(logdir: str, mono_start: float, mono_end: float,
         keep: Optional[str] = None) -> DeviceTrace:
    data = read_xplane(find_xplane(logdir))
    if keep:
        import json

        with open(keep, "w") as f:
            json.dump(data, f)
    return DeviceTrace(data, mono_start, mono_end)
