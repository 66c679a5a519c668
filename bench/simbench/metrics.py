"""Metric readers: ``bench/metrics/<name>.py``, one file per metric.

A reader defines ``read(run) -> float | None`` over a :class:`RunData`.
``None`` means it found nothing to read, and the metric is left out of
the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
from typing import Optional

from .answers import Window

READERS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


@dataclasses.dataclass
class RunData:
    spans: dict                      # host-clock seconds: setup, table_build, warmup
    window: Window
    compiles: int                    # executables built inside the window
    peak_bytes: Optional[int]
    trace: Optional[dict] = None     # trace.reduce() of the traced stretch
    trace_slots: int = 0             # simulated slots of the traced stretch


def load_reader(name: str):
    path = READERS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def collect(entries, run: RunData) -> dict:
    """``{name: {"value", "unit"}}`` of the metrics whose reader found
    something."""
    out = {}
    for m in entries:
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
