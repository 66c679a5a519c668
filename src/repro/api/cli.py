"""``python -m repro.api`` — run experiment specs from JSON.

Commands:

* ``run <spec.json> [--replicas R] [--seed S] [--out results.json]`` —
  spec file holds one experiment object or ``{"experiments": [...]}``;
  simulators are shared across experiments on the same fabric.
  ``--replicas R`` overrides every experiment's ``replicas`` (one
  vmapped batched run over R seeds instead of R sequential runs);
  ``--seed S`` overrides every experiment's base seed.  ``--ckpt-dir
  DIR`` runs a single-experiment spec through the resumable runtime
  (:mod:`repro.runtime.resilient`): engine state snapshots at every
  ``--ckpt-every`` chunk/slot boundary, and re-running the same command
  after a kill resumes bitwise from the latest snapshot.
* ``resume <ckpt_dir>`` — continue (or just report) the run stored in a
  ``--ckpt-dir`` directory, from its saved spec and latest snapshot; a
  completed run prints its stored Result without recomputation.
* ``sweep <spec.json> [--replicas R] [--seed S] [--out results.json]`` —
  spec file holds ``{"base": <experiment>, "axes": {"workload.load":
  [...], ...}}``; a seed-only axis is folded into one batched run per
  remaining grid point.
* ``serve-sweep <spec.json> [--seed S] [--out slo.json]`` — spec file
  holds one :class:`repro.serving.ServingSpec` object (``{"serving":
  {...}}`` or ``{"servings": [...]}``, bare object accepted); runs the
  open-loop load ladder and prints the p50/p99/p999 SLO curve plus the
  saturation knee per spec.  ``--out`` writes the full SLO records.
* ``degrade <spec.json> [--seed S] [--out faults.json]`` — spec file
  holds one :class:`repro.api.DegradeSpec` (``{"base": <experiment>,
  "rates": [0, 0.01, ...]}``, or ``{"sweeps": [...]}``); fails the given
  fraction of links early in warmup via one seeded
  :class:`repro.core.FailureSchedule` ladder and prints delivered
  throughput + retention per rate.
* ``search <spec.json> [--replicas R] [--seed S] [--out record.json]``
  — design-space search (:mod:`repro.search`): spec file holds one
  :class:`repro.search.SearchSpec` (``{"search": {...}}`` or bare);
  samples (family, radix, f, policy, vcs) candidates at a fixed
  endpoint count, prunes infeasible ones via the memory estimator +
  admission *before* compiling, screens the rest with short runs,
  promotes survivors to full windows (successive halving), and commits
  the Pareto frontier artifact (``--pareto-out``, default
  ``artifacts/PARETO_search.json``).
* ``estimate <spec.json> [--out est.json]`` — price every experiment's
  memory footprint (routing tables, per-replica state, transients) via
  :func:`repro.api.estimate_memory` *without* running anything — the
  pre-flight check for extreme-scale fabrics.  Each line also prints the
  predicted process peak (resident + empirical compile-RAM multiplier
  from ``BENCH_scale.json``) and warns when it exceeds host RAM.
* ``families`` — list registered topology families.
* ``patterns`` — list the workload-pattern registry (Bernoulli families,
  collectives, and which collectives compile to device-resident programs).

Each result prints as a one-line human summary on stderr-free stdout plus,
with ``--out``, the full JSON records.

Subcommands live in a declarative registry: a driver module declares a
:class:`Subcommand` (name, handler, which of the shared
``spec``/``--out``/``--replicas``/``--seed`` surface it wants, plus any
extra flags) and calls :func:`register_subcommand` at import time —
``main()`` builds its parser from the registry and never needs editing.
The shared helpers :func:`load_spec`/:func:`spec_experiments` and
:func:`emit_results`/:func:`emit_records` give every driver the same
spec-loading and output discipline.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Callable, List, Optional

from .memory import estimate_memory, format_bytes
from .runner import Result, run_all
from .registry import topology_families, workload_patterns
from .specs import Experiment
from .sweep import sweep
# registers the lm_prefill/lm_decode/lm_moe bridge patterns, so specs
# naming them load from any CLI entry point
from .. import serving

__all__ = ["Subcommand", "register_subcommand", "registered_subcommands",
           "load_spec", "spec_experiments", "emit_results", "emit_records",
           "main"]


# ---------------------------------------------------------------------- #
# subcommand registry
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Subcommand:
    """One CLI driver: parser shape + handler.

    ``fn(args) -> int`` receives the parsed namespace.  The shared flags
    are opt-in so every driver exposes the same surface with the same
    semantics: ``spec`` (positional JSON path; ``spec_name`` renames it
    for non-spec positionals like ``resume``'s checkpoint dir), ``out``
    (``--out``, the full-JSON escape hatch), ``replicas`` and ``seed``
    (spec-wide overrides).  ``configure(parser)`` adds driver-specific
    flags.
    """

    name: str
    help: str
    fn: Callable[[argparse.Namespace], int]
    spec: bool = True
    spec_name: str = "spec"
    spec_help: str = "path to the JSON spec file"
    out: Optional[str] = None          # --out help text; None = no flag
    replicas: bool = False
    seed: bool = False
    configure: Optional[Callable[[argparse.ArgumentParser], None]] = None


_SUBCOMMANDS: dict = {}


def register_subcommand(cmd: Subcommand) -> None:
    """Add ``cmd`` to the ``python -m repro.api`` dispatch table.

    Like :func:`repro.api.register_topology`: re-registering the *same*
    subcommand object is a no-op (module reloads), a different object
    under a taken name raises.
    """
    existing = _SUBCOMMANDS.get(cmd.name)
    if existing is not None and existing != cmd:
        raise ValueError(f"CLI subcommand {cmd.name!r} already registered")
    _SUBCOMMANDS[cmd.name] = cmd


def registered_subcommands() -> tuple:
    return tuple(_SUBCOMMANDS)


# ---------------------------------------------------------------------- #
# shared spec loading / result emission
# ---------------------------------------------------------------------- #
def load_spec(path: str, *, key: Optional[str] = None,
              plural: Optional[str] = None) -> list:
    """Load a JSON spec file and normalize to a list of document dicts.

    Spec files follow one convention everywhere: a bare object, or a
    wrapper holding ``{key: {...}}`` / ``{plural: [...]}`` (e.g.
    ``experiments`` / ``servings`` / ``sweeps`` / ``searches``).
    ``plural`` defaults to ``key + "s"``; pass it for irregular plurals
    (``search`` -> ``searches``).  With ``key=None`` the raw parsed
    document is returned as ``[doc]``.
    """
    with open(path) as f:
        doc = json.load(f)
    if key is None:
        return [doc]
    plural = plural or key + "s"
    if isinstance(doc, dict):
        if plural in doc:
            return list(doc[plural])
        if key in doc:
            return [doc[key]]
    return [doc]


def spec_experiments(path: str, *, replicas: Optional[int] = None,
                     seed: Optional[int] = None) -> List[Experiment]:
    """Load ``{"experiments": [...]}`` (or a bare experiment object) and
    apply the shared ``--replicas``/``--seed`` overrides."""
    exps = [Experiment.from_dict(d)
            for d in load_spec(path, key="experiment")]
    if replicas is not None:
        exps = [e.override("replicas", replicas) for e in exps]
    if seed is not None:
        exps = [e.override("seed", seed) for e in exps]
    return exps


def emit_results(results: List[Result], out: Optional[str]) -> None:
    """Print one summary line per Result; ``--out`` writes full JSON."""
    for res in results:
        print(_summary(res))
    if out:
        with open(out, "w") as f:
            json.dump([r.to_dict() for r in results], f, indent=2)
        print(f"wrote {len(results)} result(s) to {out}")


def emit_records(records: List[dict], out: Optional[str],
                 label: str = "record") -> None:
    """``--out`` writer for drivers whose records are plain dicts."""
    if out:
        with open(out, "w") as f:
            json.dump(records, f, indent=2)
        print(f"wrote {len(records)} {label}(s) to {out}")


def _summary(res: Result) -> str:
    bits = [f"{res.name}", f"metric={res.metric}"]
    if res.replica_seeds is not None:
        bits.append(f"replicas={len(res.replica_seeds)}")
    if res.offered is not None:
        bits.append(f"offered={res.offered:.3f}")
        bits.append(f"delivered={res.throughput:.3f}")
        if res.dropped:
            bits.append(f"dropped={res.dropped:g}")
    elif res.throughput is not None:
        bits.append(f"throughput={res.throughput:.3f}")
        bits.append(f"avg_hops={res.avg_hops:.2f}")
    if res.fail_drop:
        bits.append(f"fail_drop={res.fail_drop:g}")
    if res.latency is not None:
        bits.append("lat " + "/".join(f"{k}={v}" for k, v in res.latency.items()))
    if res.slots is not None:
        slots = (f"{res.slots:.1f}" if isinstance(res.slots, float)
                 else f"{res.slots}")
        bits.append(f"slots={slots}")
        bits.append(f"completed={res.completed}")
        agg = res.aggregates or {}
        if "slots" in agg:
            bits.append(f"slots_std={agg['slots']['std']:.1f}")
    return "  ".join(bits)


def _fmt_q(v) -> str:
    return "-" if v is None else f"{v:g}"


# ---------------------------------------------------------------------- #
# built-in drivers
# ---------------------------------------------------------------------- #
def _cmd_run(args) -> int:
    exps = spec_experiments(args.spec, replicas=args.replicas,
                            seed=args.seed)
    if args.ckpt_dir is not None:
        from .resume import run_resumable
        if len(exps) != 1:
            print("--ckpt-dir needs a single-experiment spec "
                  f"(got {len(exps)})", file=sys.stderr)
            return 2
        results = [run_resumable(exps[0], args.ckpt_dir,
                                 every=args.ckpt_every)]
    else:
        results = run_all(exps)
    emit_results(results, args.out)
    return 0


def _cmd_resume(args) -> int:
    from .resume import resume
    res = resume(args.ckpt_dir, every=args.ckpt_every)
    emit_results([res], args.out)
    return 0


def _cmd_sweep(args) -> int:
    doc = load_spec(args.spec)[0]
    base = Experiment.from_dict(doc["base"])
    if args.replicas is not None:
        base = base.override("replicas", args.replicas)
    if args.seed is not None:
        base = base.override("seed", args.seed)
    results = sweep(base, doc.get("axes", {}))
    emit_results(results, args.out)
    return 0


def _cmd_serve_sweep(args) -> int:
    specs = [serving.ServingSpec.from_dict(d)
             for d in load_spec(args.spec, key="serving")]
    if args.seed is not None:
        specs = [dataclasses.replace(s, seed=args.seed) for s in specs]
    records = serving.serve_sweep_many(specs)
    for rec in records:
        print(f"{rec['name']}  process={rec['spec']['process']}  "
              f"loads={len(rec['points'])}")
        for p in rec["points"]:
            print(f"  load={p['load']:g}  offered={p['offered']:.3f}  "
                  f"delivered={p['delivered']:.3f}  "
                  f"p50={_fmt_q(p.get('p50'))}  p99={_fmt_q(p.get('p99'))}  "
                  f"p999={_fmt_q(p.get('p999'))}  dropped={p['dropped']:g}")
        sat = rec["saturation"]
        print("  saturation: " + (
            f"load={sat['load']:g} (delivered/offered={sat['ratio']:.3f})"
            if sat else "none within swept loads"))
        req = rec.get("request")
        if req:
            print(f"  request: {req['model']}/{req['phase']} -> "
                  f"{req['pattern']} ranks={req['shape']['ranks']} "
                  f"packets={req['shape']['packets']} "
                  f"slots={req['slots']} completed={req['completed']}")
    emit_records(records, args.out, "SLO record")
    return 0


def _cmd_degrade(args) -> int:
    from .degrade import DegradeSpec, degrade_sweep_many
    specs = [DegradeSpec.from_dict(d)
             for d in load_spec(args.spec, key="sweep")]
    if args.seed is not None:
        specs = [dataclasses.replace(
            s, base=s.base.override("seed", args.seed)) for s in specs]
    records = degrade_sweep_many(specs)
    for rec in records:
        print(f"{rec['name']}  policy={rec['policy']}  "
              f"fail_policy={rec['fail_policy']}  links={rec['n_links']}")
        for p in rec["points"]:
            ret = ("-" if p["retention"] is None
                   else f"{p['retention']:.3f}")
            print(f"  rate={p['rate']:g}  down={p['n_links_down']}  "
                  f"delivered={p['delivered']:.3f}  retention={ret}  "
                  f"p50={_fmt_q(p.get('p50'))}  p99={_fmt_q(p.get('p99'))}  "
                  f"fail_drop={p['fail_drop']:g}")
    emit_records(records, args.out, "degradation record")
    return 0


def _cmd_estimate(args) -> int:
    exps = spec_experiments(args.spec, replicas=args.replicas)
    from .admission import (compile_ram_multiplier, host_ram_bytes,
                            predict_peak_rss)
    ram = host_ram_bytes()
    records = []
    for e in exps:
        est = estimate_memory(e)
        mult = compile_ram_multiplier(e.network.family)
        predicted = predict_peak_rss(est["total_bytes"], mult)
        est["compile_ram_multiplier"] = mult
        est["predicted_peak_rss_bytes"] = predicted
        records.append({"name": e.label(), **est})
        dims = est["dims"]
        over = (ram is not None and predicted > ram)
        print(f"{e.label()}  S={dims['n_endpoints']}  "
              f"masks={est['tables']['mask_layout']}  "
              f"tables={format_bytes(est['tables']['device_table_bytes'])} "
              f"device + {format_bytes(est['tables']['dist_leaf_bytes'])} "
              "host  "
              f"state/replica={format_bytes(est['state_bytes_per_replica'])}  "
              f"total={format_bytes(est['total_bytes'])}  "
              f"peak={format_bytes(est['peak_bytes'])}  "
              f"predicted_rss={format_bytes(predicted)} "
              f"(x{mult:.1f} compile)"
              + (f"  ** OVER host RAM {format_bytes(ram)} — admission "
                 "would refuse or downgrade **" if over else ""))
    if ram is not None:
        print(f"host RAM: {format_bytes(ram)}")
    emit_records(records, args.out, "estimate")
    return 0


def _cmd_families(_args) -> int:
    for name in topology_families():
        print(name)
    return 0


def _cmd_patterns(_args) -> int:
    for name, kind in workload_patterns():
        print(f"{name}  [{kind}]")
    print("(* = compiles to a device-resident workload program; "
          "supports schedule=barrier|window)")
    return 0


def _run_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint directory: run resumably, snapshotting "
                        "engine state at segment boundaries "
                        "(single-experiment specs only)")
    p.add_argument("--ckpt-every", type=int, default=64,
                   help="segment length between checkpoints, in engine "
                        "chunks (completion) or slots (windowed metrics); "
                        "default 64")


def _resume_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--ckpt-every", type=int, default=64,
                   help="segment length for the continued run")


register_subcommand(Subcommand(
    "run", "run experiment spec(s) from JSON", _cmd_run,
    spec_help="path to the experiment JSON file",
    out="write full Result JSON records here",
    replicas=True, seed=True, configure=_run_flags))
register_subcommand(Subcommand(
    "resume", "resume a --ckpt-dir run from its latest snapshot",
    _cmd_resume, spec_name="ckpt_dir",
    spec_help="checkpoint directory of the run",
    out="write the full Result JSON here", configure=_resume_flags))
register_subcommand(Subcommand(
    "sweep", "run a {base, axes} sweep spec", _cmd_sweep,
    spec_help="path to the sweep JSON file",
    out="write full Result JSON records here", replicas=True, seed=True))
register_subcommand(Subcommand(
    "serve-sweep", "run open-loop serving SLO sweep spec(s)",
    _cmd_serve_sweep, spec_help="path to the ServingSpec JSON file",
    out="write full SLO JSON records here", seed=True))
register_subcommand(Subcommand(
    "degrade", "run a link-failure degradation sweep spec", _cmd_degrade,
    spec_help="path to the DegradeSpec JSON file",
    out="write full degradation records here", seed=True))
register_subcommand(Subcommand(
    "estimate", "estimate memory for experiment spec(s), no run",
    _cmd_estimate, spec_help="path to the experiment JSON file",
    out="write full estimate JSON records here", replicas=True))
register_subcommand(Subcommand(
    "families", "list topology families", _cmd_families, spec=False))
register_subcommand(Subcommand(
    "patterns", "list workload patterns (shared registry)", _cmd_patterns,
    spec=False))


# ---------------------------------------------------------------------- #
# dispatch
# ---------------------------------------------------------------------- #
def main(argv: Optional[List[str]] = None) -> int:
    from ..runtime.compile_cache import use_compile_cache
    use_compile_cache()
    parser = argparse.ArgumentParser(prog="python -m repro.api",
                                     description=__doc__.splitlines()[0])
    sub = parser.add_subparsers(dest="command", required=True)
    for cmd in _SUBCOMMANDS.values():
        p = sub.add_parser(cmd.name, help=cmd.help)
        if cmd.spec:
            p.add_argument(cmd.spec_name, help=cmd.spec_help)
        if cmd.out is not None:
            p.add_argument("--out", help=cmd.out)
        if cmd.replicas:
            p.add_argument("--replicas", type=int, default=None,
                           help="override replicas (>= 1): one vmapped "
                                "batched run over R seeds per experiment")
        if cmd.seed:
            p.add_argument("--seed", type=int, default=None,
                           help="override the spec's base seed")
        if cmd.configure is not None:
            cmd.configure(p)
        p.set_defaults(fn=cmd.fn)
    args = parser.parse_args(argv)
    return args.fn(args)


# the search driver registers its own subcommand on import (the registry
# is populated above, so this import must stay below the definitions)
from .. import search as _search  # noqa: E402,F401  (registration side effect)


if __name__ == "__main__":
    sys.exit(main())
