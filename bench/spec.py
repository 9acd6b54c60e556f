"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
each lives in a file of its own: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/limits/<cell>.json`` (the limits of
the correctness comparison) and ``bench/metrics/<metric>.py`` (one reader per
per-layer metric). Adding a cell, a mix or a metric is adding files and
entries; nothing here names one.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def resolve(name: str, bench: Dict[str, Any]) -> Cell:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.relpath(os.path.join(ROOT, configs[w["config"]]["file"]), BENCH))
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=_json("traffic", f"{w['traffic']}.json"),
        limits=_json("limits", f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)],
    )


def reader(metric: str) -> Callable[[Any], Any]:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def derive(seed: int, tag: str, bits: int = 63) -> int:
    """A seed of its own for each use of the run's ``--seed``."""
    h = hashlib.blake2b(f"{tag}:{seed}".encode(), digest_size=8).digest()
    return int.from_bytes(h, "little") & ((1 << bits) - 1)
