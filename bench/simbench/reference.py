"""Plain reference of what a configuration and a traffic mix guarantee.

Independent of the simulator: it imports nothing of ``repro`` and takes
nothing that the simulator built.  It works from the numbers in the
configuration and traffic files alone:

* ``completion_bounds``: a program of ``rounds`` rounds gives every
  endpoint one packet a round, and an endpoint injects at most one packet
  a slot, so the ``(p + 1) * S`` deliveries that complete phase ``p``
  under the window schedule take at least ``p + 1`` slots.
* ``uniform_mean_hops``: the mean number of switch-to-switch links on a
  shortest path between a source and a destination drawn uniformly over
  all ``S`` endpoints, from the reference's own build of the fabric and
  its own breadth-first search.  Minimal routing delivers every packet
  along such a path; any routing delivers none along a shorter one.
* ``shifted_exchange_hops``: the least and the most links that the
  ``S * rounds`` packets of a shifted exchange (round ``r`` sends endpoint
  ``e`` to ``(e + r + 1) mod S``) cross in all.  Endpoint ``e`` sits on
  leaf ``e // (endpoints per leaf)``.  On the Fat-Tree both are the sum of
  shortest paths, which minimal routing has to meet exactly.  On a
  leaf-spine fabric (MRLS) two leaves are at least two links apart, and
  the route crosses at most ``max_hops``.
"""
from __future__ import annotations

import itertools
from typing import Optional

import numpy as np


def completion_bounds(rounds: int) -> list:
    """Least cumulative completion slot of each phase of an All2All
    program of one packet per endpoint and round."""
    return [p + 1 for p in range(int(rounds))]


# ---------------------------------------------------------------------- #
# fabrics
# ---------------------------------------------------------------------- #
def fat_tree_graph(radix: int, h: int, a1: Optional[int] = None):
    """Folded Clos of ``h + 1`` switch levels.

    An endpoint has digits ``(a_1, .., a_h)``, ``a_1 < A1`` (default
    ``radix``) and the others ``< k = radix / 2``; a level-``l`` switch is
    named by the first ``h - l`` endpoint digits and ``l`` up-port digits,
    and its up-port ``p`` leads to the level-``l + 1`` switch that drops
    the last endpoint digit and appends ``p``.  Leaves (level 0) hold
    ``k`` endpoints.  Returns ``(n_switches, edges, leaves, k)``.
    """
    k = radix // 2
    A1 = radix if a1 is None else a1
    ids: dict = {}

    def sid(level, a, p):
        return ids.setdefault((level, a, p), len(ids))

    edges = []
    for level in range(h):
        a_ranges = [range(A1)] + [range(k)] * (h - level - 1)
        for a in itertools.product(*a_ranges):
            for p in itertools.product(*([range(k)] * level)):
                me = sid(level, a, p)
                for q in range(k):
                    edges.append((me, sid(level + 1, a[:-1], p + (q,))))
    leaves = [i for key, i in ids.items() if key[0] == 0]
    return len(ids), np.asarray(edges, np.int64), np.asarray(leaves), k


def leaf_distances(n: int, edges: np.ndarray, leaves: np.ndarray) -> np.ndarray:
    """Hop distance ``[len(leaves), len(leaves)]`` by breadth-first search
    from every leaf at once (dense frontier products)."""
    adj = np.zeros((n, n), np.float32)
    adj[edges[:, 0], edges[:, 1]] = 1.0
    adj[edges[:, 1], edges[:, 0]] = 1.0
    dist = np.full((len(leaves), n), -1, np.int64)
    seen = np.zeros((len(leaves), n), bool)
    frontier = np.zeros((len(leaves), n), np.float32)
    frontier[np.arange(len(leaves)), leaves] = 1.0
    d = 0
    while frontier.any():
        hit = frontier > 0
        dist[hit & ~seen] = d
        seen |= hit
        frontier = ((frontier @ adj) > 0) & ~seen
        frontier = frontier.astype(np.float32)
        d += 1
    if (dist[:, leaves] < 0).any():
        raise ValueError("fabric is not connected")
    return dist[:, leaves]


def uniform_mean_hops(config: dict) -> Optional[float]:
    """Mean shortest-path hops of uniform traffic on the configured fabric,
    or ``None`` for a family the reference does not build."""
    if config["family"] != "fat_tree":
        return None
    p = config["params"]
    n, edges, leaves, per_leaf = fat_tree_graph(int(p["radix"]), int(p["h"]),
                                                p.get("a1"))
    S = len(leaves) * per_leaf
    if S != int(config["endpoints"]):
        raise ValueError(f"reference fabric has {S} endpoints, the "
                         f"configuration states {config['endpoints']}")
    dist = leaf_distances(n, edges, leaves)
    # every leaf holds the same number of endpoints: the endpoint-pair
    # mean is the leaf-pair mean
    return float(dist.mean())


def _shifted_leaves(S: int, per_leaf: int, rounds: int):
    e = np.arange(S, dtype=np.int64)
    return [(e // per_leaf, ((e + r + 1) % S) // per_leaf)
            for r in range(int(rounds))]


def shifted_exchange_hops(config: dict, rounds: int) -> Optional[tuple]:
    """``(least, most)`` links crossed by all packets of a shifted
    exchange of ``rounds`` rounds, or ``None`` for a family the reference
    does not know."""
    S = int(config["endpoints"])
    p = config["params"]
    if config["family"] == "fat_tree":
        n, edges, leaves, per_leaf = fat_tree_graph(
            int(p["radix"]), int(p["h"]), p.get("a1"))
        if len(leaves) * per_leaf != S:
            raise ValueError(f"reference fabric has {len(leaves) * per_leaf}"
                             f" endpoints, the configuration states {S}")
        dist = leaf_distances(n, edges, leaves)
        total = sum(int(dist[a, b].sum())
                    for a, b in _shifted_leaves(S, per_leaf, rounds))
        return total, total
    if config["family"] == "mrls":
        per_leaf = int(p["d"])
        if int(p["n_leaves"]) * per_leaf != S:
            raise ValueError("leaves x endpoints per leaf != endpoints")
        cross = sum(int((a != b).sum())
                    for a, b in _shifted_leaves(S, per_leaf, rounds))
        return 2 * cross, int(config["route"]["max_hops"]) * cross
    return None
