"""BENCHMARK.json: every entry resolves to its files, and names, units and
sizes keep to the manifest's rules."""
import json
import re

import pytest

import benchtiny
from simbench import cells

MANIFEST = json.loads((benchtiny.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_workload_resolves(cell):
    c = cells.resolve(cell)
    assert c.chips in (1, 4)
    for key in ("family", "params", "route", "endpoints", "guarantees"):
        assert key in c.config
    for key in ("workload", "metric", "replicas", "slots", "limits"):
        assert key in c.traffic
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


def test_files_are_the_manifests():
    for c in MANIFEST["configs"]:
        cfg = json.loads((benchtiny.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert c["file"].startswith("bench/")
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_has_reader_and_legal_names(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert (benchtiny.BENCH / "metrics" / f"{metric['name']}.py").is_file()
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}


def test_names_and_sizes():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in MANIFEST[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_answer_seeds():
    big = 2 ** 31 + 12345
    seeds = [cells.answer_seed(big, i) for i in range(-1, 500)]
    assert seeds == [cells.answer_seed(big, i) for i in range(-1, 500)]
    assert len(set(seeds)) == len(seeds)
    assert all(1 <= s <= cells.SEED_SPAN for s in seeds)
    assert cells.answer_seed(big, 0) != cells.answer_seed(big + 1, 0)
