"""Cells of ``BENCHMARK.json``, resolved to their files by name.

A cell names a configuration (``configs[].file``) and a traffic mix
(``bench/traffic/<mix>.json``).  Nothing here is specific to one cell, so
a new cell is a new ``workloads`` entry plus the files it names.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
# answer seeds stay below 2**31 with room for ``replicas`` consecutive seeds
SEED_SPAN = 2 ** 31 - 2 ** 16


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # manifest entries of the metrics this cell reports
    per_layer: tuple


def load_manifest(root: pathlib.Path = ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def reports(metric: dict, cell: str) -> bool:
    """A metric without ``workloads`` is reported by every cell."""
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: pathlib.Path = ROOT) -> Cell:
    root = pathlib.Path(root)
    m = load_manifest(root)
    cells = {w["name"]: w for w in m["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in m["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(x for x in m["end_to_end"] if reports(x, name)),
        per_layer=tuple(x for x in m["per_layer"] if reports(x, name)))


def answer_seed(base: int, i: int) -> int:
    """Seed of answer ``i`` of a run started with ``--seed base``.

    Any whole ``base``, however large, maps to ``[1, SEED_SPAN]``; the
    warm-up answer uses ``i = -1``.
    """
    h = hashlib.sha256(f"{int(base)}:{int(i)}".encode()).digest()
    return 1 + int.from_bytes(h[:8], "little") % SEED_SPAN


def experiment_dict(cell: Cell, seed: int, route: dict | None = None) -> dict:
    """The ``repro.api.Experiment`` of one answer, as its JSON form."""
    c, t = cell.config, cell.traffic
    d = {"network": {"family": c["family"], "params": c["params"]},
         "route": dict(route if route is not None else c["route"]),
         "workload": dict(t["workload"]),
         "name": cell.name, "metric": t["metric"], "seed": int(seed),
         "replicas": int(t.get("replicas", 1))}
    for k in ("warm", "measure", "chunk", "max_slots"):
        if k in t:
            d[k] = int(t[k])
    return d
