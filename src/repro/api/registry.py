"""String-keyed registries behind the declarative layer.

* Topology builders: seeds from :data:`repro.core.TOPOLOGY_BUILDERS` (the
  six paper families) and accepts user registrations, so downstream code
  can declare fabrics by name in JSON without importing builder functions.
* Workload patterns: re-exported views of the shared pattern registry
  (:mod:`repro.workloads.patterns`) that ``WorkloadSpec`` and the engine
  both validate against, plus the collective -> program builder table
  (:data:`repro.workloads.programs.PROGRAM_BUILDERS`).
"""
from __future__ import annotations

from typing import Callable, Optional

from ..core import TOPOLOGY_BUILDERS
from ..core.topology import Topology
from ..runtime import tracing
from ..workloads.patterns import pattern_kinds
from ..workloads.programs import PROGRAM_BUILDERS
from .specs import NetworkSpec

__all__ = ["register_topology", "topology_families", "build_network",
           "workload_patterns"]


def workload_patterns() -> tuple:
    """``(name, kind)`` pairs for every spec-level workload pattern, sorted
    by name.  Collectives marked ``collective*`` compile to device-resident
    workload programs."""
    out = []
    for name, kind in sorted(pattern_kinds().items()):
        if kind == "engine":
            continue                       # not reachable from WorkloadSpec
        if kind == "collective" and name in PROGRAM_BUILDERS:
            kind = "collective*"
        out.append((name, kind))
    return tuple(out)

_REGISTRY: dict = dict(TOPOLOGY_BUILDERS)


def register_topology(name: str, builder: Callable[..., Topology],
                      *, overwrite: bool = False) -> None:
    """Register ``builder`` under ``name`` for NetworkSpec resolution.

    Re-registering the *same* builder object under its existing name is a
    no-op (module reloads and interactive sessions hit this path);
    registering a *different* builder under a taken name still raises
    unless ``overwrite=True``.
    """
    if name in _REGISTRY and not overwrite:
        if _REGISTRY[name] is builder:
            return
        raise ValueError(f"topology family {name!r} already registered "
                         "with a different builder (pass overwrite=True "
                         "to replace it)")
    _REGISTRY[name] = builder


def topology_families() -> tuple:
    return tuple(sorted(_REGISTRY))


def build_network(spec: NetworkSpec) -> Topology:
    """Resolve ``spec.family`` and build the topology from ``spec.params``."""
    try:
        builder = _REGISTRY[spec.family]
    except KeyError:
        raise KeyError(
            f"unknown topology family {spec.family!r}; known: "
            f"{topology_families()}") from None
    with tracing.span("topology.build"):
        return builder(**spec.param_dict())
