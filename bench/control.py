#!/usr/bin/env python3
"""Readings of the comparison that decides ``correct``, in one process.

    python bench/control.py --workload <cell> --seconds <s> \\
        --plan sound:1,2,3 --plan lossy:4,5,6

For each ``kind:seeds`` plan entry, sets the cell up once and runs, per
seed, a window of ``--seconds`` as ``run.py`` does (the first window
compiles: no timing is read here) and prints the
comparison's numbers as one JSON line.  ``sound`` is the simulator as
configured; the other kinds are the controls of ``simbench.controls``.
The last line gives, for each number, the largest sound reading and the
smallest control reading: the two a limit is set between.

Not run by the benchmark's runs.  Exits non-zero off a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from simbench import cells, check, chip, controls, loop, probe  # noqa: E402


def plan(text: str):
    kind, _, seeds = text.partition(":")
    if kind != "sound" and kind not in controls.KINDS:
        raise argparse.ArgumentTypeError(f"unknown kind {kind!r}")
    return kind, [int(s) for s in seeds.split(",") if s]


def readings(cell, kind: str, seeds, seconds: float, *,
             out=print) -> list:
    """One line per seed: ``{"kind", "seed", "answers", "checks"}``."""
    from repro.api import SimulatorCache
    route = controls.route(kind, cell.config)
    cell = dataclasses.replace(cell, traffic=controls.traffic(kind,
                                                              cell.traffic))
    ref = check.reference_for(cell.traffic, cell.config)
    lines = []
    with controls.patched(kind), SimulatorCache() as sims, \
            probe.tally([]) as sink:
        for seed in seeds:
            win = loop.window(cell, sims, seed, seconds, used=set(),
                              sink=sink, route=route)
            win.fetch_counts()
            checks = check.compare(win.records(), win.raised, cell.traffic,
                                   cell.config, ref)
            line = {"kind": kind, "seed": seed, "answers": win.attempted,
                    "failed": win.failed,
                    "checks": {c.name: c.value for c in checks},
                    "correct": all(c.ok for c in checks)}
            lines.append(line)
            out(json.dumps(line))
    return lines


def summary(lines: list) -> dict:
    sound = [ln for ln in lines if ln["kind"] == "sound"]
    ctrl = [ln for ln in lines if ln["kind"] != "sound"]
    names = sorted({k for ln in lines for k in ln["checks"]})
    out = {}
    for k in names:
        s = [ln["checks"][k] for ln in sound if k in ln["checks"]]
        c = [ln["checks"][k] for ln in ctrl if k in ln["checks"]]
        out[k] = {"sound_max": max(s) if s else None,
                  "control_min": min(c) if c else None}
    out["sound_correct"] = all(ln["correct"] for ln in sound)
    out["controls_not_correct"] = not any(ln["correct"] for ln in ctrl)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--plan", type=plan, action="append", required=True)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)

    chip.compile_cache()
    try:
        chip.tpu_device(cell.chips)
    except chip.NoDevice as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    t = time.perf_counter()
    lines = []
    for kind, seeds in args.plan:
        lines += readings(cell, kind, seeds, args.seconds)
        print(f"# {kind} done at {time.perf_counter() - t:.1f} s",
              file=sys.stderr, flush=True)
    print(json.dumps({"workload": cell.name, "summary": summary(lines)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
