"""Shared benchmark harness.

Every ``bench_*`` module maps to one paper table/figure and exposes::

    NAME      — short id
    PAPER_REF — which table/figure it reproduces
    def run(scale: Scale) -> Result

``Result.rows`` is a list of flat dicts (one per measured cell) and
``Result.claims`` a list of (description, bool) paper-claim validations.
``run.py`` renders tables, writes ``reports/bench/<name>.json`` and prints a
claim summary.  Remote storage is the calibrated :class:`SimulatedS3Store`;
"scratch" is the in-memory/local path.  These benchmarks run wherever jax
runs and have so far only been run on the CPU: their timings are host
figures (loader, storage, CPU stage), never device figures.  Counts they
check (bytes copied or fetched, bit-identity) hold on any backend.  The only
chip-side check is ``chip_smoke.py`` at the repo root.
"""
from __future__ import annotations

import json
import os
import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple


from repro.config import LoaderConfig, PipelineConfig
from repro.core import make_loader as _core_make_loader
from repro.core.loader import ConcurrentDataLoader
from repro.core.tracing import NULL_TRACER, Tracer
from repro.data.dataset import ImageDataset
from repro.data.imagenet_synth import build_synthetic_imagenet
from repro.data.store import (
    CachedStore,
    DiskTierCache,
    InMemoryStore,
    MemoryTierCache,
    ObjectStore,
    SimulatedS3Store,
    TieredCacheStore,
    make_admission,
)

# --------------------------------------------------------------------------
# scale presets
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Scale:
    """Benchmark scale knobs.  ``quick`` keeps the full suite ~15 min on CI;
    ``full`` stretches datasets/epochs for tighter statistics."""

    name: str = "quick"
    dataset_items: int = 384
    batch_size: int = 32
    epochs: int = 2
    avg_kb: float = 48.0
    # calibrated network model (see DESIGN.md §2): ~20 ms median GET,
    # per-connection 50 MB/s, 1.2 GB/s NIC
    latency_mean_s: float = 0.02
    latency_sigma: float = 0.5
    bandwidth_per_conn: float = 50e6
    nic_bandwidth: float = 1.2e9
    max_connections: int = 256
    repeats: int = 1


QUICK = Scale()
FULL = Scale(
    name="full", dataset_items=1024, epochs=3, repeats=3,
)


def paper_scale(scale: Scale, items: int = 256) -> Scale:
    """Table-3 calibration: the paper's ~80 ms median S3 GET (the regime
    where a V100 step is ~100x faster than a batch load), smaller dataset so
    the vanilla-s3 cells stay tractable on CI."""
    import dataclasses

    return dataclasses.replace(
        scale, latency_mean_s=0.08, dataset_items=min(scale.dataset_items, items)
    )


@dataclass
class Result:
    name: str
    paper_ref: str
    rows: List[Dict[str, Any]] = field(default_factory=list)
    claims: List[Tuple[str, bool]] = field(default_factory=list)
    notes: str = ""
    wall_s: float = 0.0


# --------------------------------------------------------------------------
# dataset / store builders
# --------------------------------------------------------------------------

_IMAGE_CACHE: Dict[Tuple[int, float], InMemoryStore] = {}


def base_image_store(scale: Scale, num_items: Optional[int] = None) -> InMemoryStore:
    """Deterministic synthetic-ImageNet blob store (shared across benches)."""
    n = num_items or scale.dataset_items
    key = (n, scale.avg_kb)
    if key not in _IMAGE_CACHE:
        _IMAGE_CACHE[key] = build_synthetic_imagenet(
            InMemoryStore(), num_items=n, avg_kb=scale.avg_kb
        )
    return _IMAGE_CACHE[key]


def make_store(
    kind: str,
    scale: Scale,
    *,
    num_items: Optional[int] = None,
    cache_bytes: int = 0,
    disk_dir: str = "",
    disk_bytes: int = 0,
    admission: str = "admit-all",
    cache_shards: int = 1,
    seed: int = 0,
    tracer: Optional[Tracer] = None,
) -> ObjectStore:
    """kind: 'scratch' (in-memory local) | 's3' (simulated remote).

    ``cache_bytes`` alone keeps the legacy single-tier ``CachedStore``;
    adding ``disk_dir`` builds the two-tier ``TieredCacheStore`` (memory LRU
    over a disk tier bounded at ``disk_bytes``, 0 = unbounded)."""
    base = base_image_store(scale, num_items)
    store: ObjectStore = base
    if kind == "s3":
        store = SimulatedS3Store(
            base,
            latency_mean_s=scale.latency_mean_s,
            latency_sigma=scale.latency_sigma,
            bandwidth_per_conn=scale.bandwidth_per_conn,
            nic_bandwidth=scale.nic_bandwidth,
            max_connections=scale.max_connections,
            seed=seed,
        )
    if disk_dir:
        store = TieredCacheStore(
            store,
            memory=(
                MemoryTierCache(cache_bytes, shards=cache_shards)
                if cache_bytes else None
            ),
            disk=DiskTierCache(disk_dir, disk_bytes, make_admission(admission)),
            tracer=tracer or NULL_TRACER,
        )
    elif cache_bytes:
        store = CachedStore(store, cache_bytes)
    return store


# paper-calibrated simulated decode: ~6 ms per 115 kB ImageNet JPEG
DECODE_S_PER_MB = 0.052


def make_image_dataset(
    store: ObjectStore,
    scale: Scale,
    *,
    num_items: Optional[int] = None,
    out_size: int = 96,
    tracer: Optional[Tracer] = None,
) -> ImageDataset:
    return ImageDataset(
        store,
        num_items or scale.dataset_items,
        out_size=out_size,
        tracer=tracer or Tracer(),
        sim_decode_s_per_mb=DECODE_S_PER_MB,
    )


_PIPELINE_KW = (
    "reorder", "reorder_window", "io_workers", "cpu_workers",
    "cpu_executor", "stage_queue_depth",
)


def nest_loader_kwargs(overrides: Dict[str, Any]) -> Dict[str, Any]:
    """Nest the historical flat pipeline kwargs (bench tables keep the flat
    spelling for brevity) into ``PipelineConfig``, so bench runs construct
    the nested config directly instead of tripping the deprecation shim.
    Returns a new kwargs dict; ``overrides`` is not mutated."""
    out = dict(overrides)
    pipe_kw = {k: out.pop(k) for k in _PIPELINE_KW if k in out}
    pipeline = out.pop("pipeline", None)
    if pipeline is None or isinstance(pipeline, bool):
        pipeline = PipelineConfig(enabled=bool(pipeline), **pipe_kw)
    elif pipe_kw:
        import dataclasses

        pipeline = dataclasses.replace(pipeline, **pipe_kw)
    out["pipeline"] = pipeline
    return out


def make_loader(
    dataset: ImageDataset,
    impl: str,
    scale: Scale,
    *,
    tracer: Optional[Tracer] = None,
    **overrides: Any,
) -> ConcurrentDataLoader:
    """Bench front-end over :func:`repro.core.make_loader`."""
    overrides = nest_loader_kwargs(overrides)
    cfg = LoaderConfig(
        impl=impl,
        batch_size=overrides.pop("batch_size", scale.batch_size),
        num_workers=overrides.pop("num_workers", 4),
        prefetch_factor=overrides.pop("prefetch_factor", 4),
        num_fetch_workers=overrides.pop("num_fetch_workers", 16),
        **overrides,
    )
    return _core_make_loader(cfg, dataset, tracer=tracer or Tracer())


# --------------------------------------------------------------------------
# measurement helpers
# --------------------------------------------------------------------------


def drain_loader(loader: ConcurrentDataLoader, epochs: int = 1) -> Dict[str, float]:
    """Consume every batch; return wall time + item/byte throughput
    (the paper's img/s and Mbit/s units)."""
    t0 = time.monotonic()
    items = 0
    nbytes = 0
    for epoch in range(epochs):
        if epoch:
            loader.set_epoch(epoch)
        for batch in loader:
            items += len(batch["label"])
            nbytes += int(batch["nbytes"].sum())
    wall = time.monotonic() - t0
    return {
        "runtime_s": round(wall, 3),
        "img_per_s": round(items / wall, 2),
        "mbit_per_s": round(nbytes * 8 / 1024**2 / wall, 2),
        "items": items,
    }


def median(xs: Sequence[float]) -> float:
    return statistics.median(xs) if xs else float("nan")


def pctl(xs: Sequence[float], q: float) -> float:
    if not xs:
        return float("nan")
    s = sorted(xs)
    return s[min(int(q * len(s)), len(s) - 1)]


# --------------------------------------------------------------------------
# table rendering / persistence
# --------------------------------------------------------------------------


def render_table(rows: List[Dict[str, Any]]) -> str:
    if not rows:
        return "(no rows)"
    cols = list(rows[0].keys())
    widths = {
        c: max(len(str(c)), *(len(_fmt(r.get(c))) for r in rows)) for c in cols
    }
    head = " | ".join(str(c).ljust(widths[c]) for c in cols)
    sep = "-+-".join("-" * widths[c] for c in cols)
    body = "\n".join(
        " | ".join(_fmt(r.get(c)).ljust(widths[c]) for c in cols) for r in rows
    )
    return f"{head}\n{sep}\n{body}"


def _fmt(v: Any) -> str:
    if isinstance(v, float):
        if v != v:  # nan
            return "nan"
        if abs(v) >= 1000 or (abs(v) < 0.01 and v != 0):
            return f"{v:.3g}"
        return f"{v:.3f}".rstrip("0").rstrip(".")
    return str(v)


def save_result(result: Result, out_dir: str = "reports/bench") -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{result.name}.json")
    with open(path, "w") as f:
        json.dump(
            {
                "name": result.name,
                "paper_ref": result.paper_ref,
                "rows": result.rows,
                "claims": [{"claim": c, "ok": bool(ok)} for c, ok in result.claims],
                "notes": result.notes,
                "wall_s": result.wall_s,
            },
            f,
            indent=1,
            default=str,
        )
    return path
