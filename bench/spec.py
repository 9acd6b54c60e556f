"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
each lives in a file of its own: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/limits/<cell>.json`` (the limits of
the correctness comparison), ``bench/metrics/<metric>.py`` (one reader per
per-layer metric) and ``bench/families/<family>.py`` (what a model family's
samples, program and references are; the configuration's ``family`` key
names it). Adding a cell, a mix, a metric or a family is adding files and
entries; nothing here names one.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
FAMILIES = os.path.join(BENCH, "families")


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
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
    family: ModuleType  # bench/families/<config's family>.py


def resolve(name: str, bench: Dict[str, Any], root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic, limits, the
    metrics it reports and its family's module, from the files of the
    checkout at ``root`` (the harness's tests give a fixture laid out as the
    benchmark is)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root, configs[w["config"]]["file"])
    base = os.path.join(root, "bench")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=_json(base, "traffic", f"{w['traffic']}.json"),
        limits=_json(base, "limits", f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)],
        family=family(config.get("family"), os.path.join(base, "families")),
    )


def _load(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable[[Any], Any]:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return _load(os.path.join(BENCH, "metrics", f"{metric}.py"), f"bench_metric_{metric}").read


def family(name: str, directory: str = FAMILIES) -> ModuleType:
    """The module ``<directory>/<name>.py``: a model family's samples,
    program and references (the interface is in
    ``bench/families/__init__.py``). ``name`` is a configuration's ``family``
    key."""
    if not name:
        raise KeyError("the configuration has no 'family' key: it names the module "
                       "bench/families/<family>.py that runs it")
    file = os.path.join(directory, f"{name}.py")
    if not os.path.exists(file):
        raise KeyError(f"no module for family {name!r}: no {file}")
    return _load(file, f"bench_family_{name}")


def derive(seed: int, tag: str, bits: int = 63) -> int:
    """A seed of its own for each use of the run's ``--seed``."""
    h = hashlib.blake2b(f"{tag}:{seed}".encode(), digest_size=8).digest()
    return int.from_bytes(h, "little") & ((1 << bits) - 1)
