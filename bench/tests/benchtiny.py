"""Tiny cells for the benchmark's CPU tests.

``tiny_root`` writes a checkout-like directory whose ``BENCHMARK.json``
names tiny fabrics under the benchmark's own traffic mixes, so the
harness runs end to end on the CPU in seconds.
"""
from __future__ import annotations

import copy
import importlib.util
import io
import json
import pathlib
import sys
from contextlib import redirect_stdout

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

GUARANTEES = {"lossless": "every packet a program sends is delivered"}
CONFIGS = {
    "tiny_ft": {"family": "fat_tree", "params": {"radix": 8, "h": 2},
                "route": {"policy": "minimal_adaptive", "max_hops": 4},
                "endpoints": 128, "switches": 80,
                "guarantees": dict(GUARANTEES, minimal_routes=True)},
    "tiny_mrls": {"family": "mrls",
                  "params": {"n_leaves": 14, "u": 3, "d": 3, "seed": 0},
                  "route": {"policy": "polarized", "max_hops": 10,
                            "pool": 4096},
                  "endpoints": 42, "switches": 21,
                  "guarantees": dict(GUARANTEES, minimal_routes=False)},
}
CELLS = {"a2a_w4.tiny_mrls": ("tiny_mrls", "a2a_w4"),
         "a2a_w4.tiny_ft": ("tiny_ft", "a2a_w4"),
         "uniform_sat.tiny_ft": ("tiny_ft", "uniform_sat")}


def load(script: str):
    """``bench/<script>.py`` as the module ``bench_<script>``."""
    name = f"bench_{script}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, BENCH / f"{script}.py")
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def traffic(mix: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{mix}.json").read_text())


def tiny_root(tmp: pathlib.Path, mixes: dict | None = None) -> pathlib.Path:
    """A directory with ``BENCHMARK.json``, tiny configurations and the
    benchmark's traffic mixes (``mixes`` replaces some of them)."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] = [
        {"name": n, "source": "https://arxiv.org/abs/2605.26960",
         "file": f"bench/configs/{n}.json", "reduced": [], "why": "test"}
        for n in CONFIGS]
    manifest["workloads"] = [
        {"name": c, "config": cfg, "traffic": mix, "chips": 1, "why": "test"}
        for c, (cfg, mix) in CELLS.items()]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    (tmp / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    for n, c in CONFIGS.items():
        (tmp / "bench" / "configs" / f"{n}.json").write_text(json.dumps(c))
    for mix in ("a2a_w4", "uniform_sat"):
        t = copy.deepcopy((mixes or {}).get(mix) or traffic(mix))
        (tmp / "bench" / "traffic" / f"{mix}.json").write_text(json.dumps(t))
    return tmp


def cpu_device(chips: int) -> dict:
    import jax
    return {"platform": jax.devices()[0].platform, "kind": "cpu",
            "count": len(jax.devices())}


def run_cell(root: pathlib.Path, cell: str, *, seed: int = 7,
             seconds: float = 0.5, trace: int = 0) -> tuple:
    """Drive ``bench/run.py``'s ``main`` past its chip check; returns
    ``(exit code, last stdout line as JSON or None)``."""
    import time

    out = io.StringIO()
    with redirect_stdout(out):
        rc = load("run").main(["--workload", cell, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace",
                             str(trace)], root=root, device=cpu_device,
                           t0=time.perf_counter())
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
