"""BENCHMARK.json resolves to the files the harness finds by name, and
keeps to the shape the benchmark's contract gives it."""
import json
import os
import re

import pytest

from bench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _cells():
    return [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_cell_resolves_to_its_files(cell):
    c = spec.resolve(cell, BENCH)
    assert c.family.samples_per_step(c.config) > 0
    assert c.traffic["storage"]["kind"] in ("s3", "local")
    assert c.limits and all(v >= 0 for v in c.limits.values())
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_has_a_reader(metric):
    assert callable(spec.reader(metric))


def test_names_units_and_sources():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = set(_cells())
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_configs_and_cells_are_used_and_unique():
    configs = {c["name"]: c for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in configs.values():
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(spec.ROOT, c["file"]))
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(pairs) // 2)


def test_run_seconds_fit_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
