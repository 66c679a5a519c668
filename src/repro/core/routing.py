"""Routing for randomly-wired indirect networks (Section 4.3 of the paper).

Host-side (numpy): BFS distance tables and a reference step-by-step router
used by tests and analytics.  Device-side (jnp): vectorized Polarized port
scoring used by the cycle-level simulator.

Polarized routing (Camarero et al. [28], adapted to indirect networks here):
every candidate next-hop link is classified by the tuple
``(d(n,s)-d(c,s), d(n,t)-d(c,t))`` into Forward(+1,-1) / Expansion(+1,+1) /
Contraction(-1,-1) / Backtrack(-1,+1).  Forward is always allowed; Expansion
only while ``d(c,s) < d(c,t)``; Contraction only once ``d(c,s) >= d(c,t)``;
Backtrack never.  Theorem 4.2 bounds route length by ``2 D* - 2``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from ..runtime import tracing
from .topology import Topology

__all__ = [
    "bfs_distances",
    "RoutingTables",
    "TableDelta",
    "build_tables",
    "pack_port_masks",
    "iter_port_mask_blocks",
    "mask_table_bytes",
    "route_row_words",
    "pack_route_rows",
    "polarized_port_mask",
    "route_packet_host",
    "POLICIES",
    "FUSED_POLICIES",
    "MASK_LAYOUTS",
    "DENSE_MASK_LIMIT",
    "UNREACHABLE",
]

POLICIES = ("polarized", "minimal_adaptive", "ksp", "ugal", "valiant",
            "degraded")

# policies that read away bits and distances: the simulator keeps one
# fused route-row table for them (:func:`pack_route_rows`) in place of the
# toward-bit and distance tables of the minimal policies
FUSED_POLICIES = ("polarized", "degraded")

MASK_LAYOUTS = ("auto", "dense", "blocked")

# Sentinel distance for switches unreachable after failures.  Chosen so it
# (a) stays >= 0 — the engine's pristine-construction assert and every
# ``d >= 0`` check pass — and (b) sits far above any real diameter yet far
# below int16 overflow, so ``d - 1`` / ``d + 1`` comparisons against real
# distances are always false and hop-budget tests always fail (a packet is
# never steered toward an unreachable switch).
UNREACHABLE = 16384

# ``masks="auto"`` switches to the blocked (streamed) layout once one dense
# numpy mask table would exceed this many bytes — small fabrics keep the
# dense arrays around for host-side tooling, paper-scale fabrics never
# materialize them.
DENSE_MASK_LIMIT = 256 * 1024 * 1024


# ---------------------------------------------------------------------- #
# distances
# ---------------------------------------------------------------------- #
def bfs_distances(topo: Topology, sources: np.ndarray, *,
                  nbrs: Optional[np.ndarray] = None) -> np.ndarray:
    """[len(sources), N] int16 hop distances (-1 = unreachable).

    Per-source frontier BFS with vectorized neighbor expansion; fast enough
    for the paper's 100K-endpoint networks (~6K sources x ~9K switches).
    The TPU-resident alternative is tropical matrix powering — see
    ``repro.kernels.minplus`` (the Pallas hot-spot kernel).

    ``nbrs`` overrides the adjacency (same ``[N, P]`` -1-padded layout) —
    the delta-rebuild path passes an *effective* adjacency with failed
    links/switches masked out without mutating the topology.
    """
    nbrs = topo.nbrs if nbrs is None else nbrs
    n, p = topo.n_switches, nbrs.shape[1]
    sources = np.asarray(sources, dtype=np.int64)
    k = len(sources)
    out = np.full((k, n), -1, np.int16)
    # level-synchronous over source *blocks*: expand every block member's
    # frontier in one scatter per hop level — work proportional to the
    # frontier population (not B*N*P), which is what makes the
    # delta-rebuild path cheap when only a few leaf rows changed.  The
    # block bounds the per-level index arrays at the 100k scale points.
    block = 256
    for lo in range(0, k, block):
        hi = min(lo + block, k)
        b = hi - lo
        frontier = np.zeros((b, n), bool)
        frontier[np.arange(b), sources[lo:hi]] = True
        visited = frontier.copy()
        dist = out[lo:hi]
        d = 0
        while True:
            rows, nodes = np.nonzero(frontier)
            if rows.size == 0:
                break
            dist[rows, nodes] = d
            cand = nbrs[nodes]                       # [F, P]
            ok = (cand >= 0).ravel()
            nxt = np.zeros_like(frontier)
            nxt[np.repeat(rows, p)[ok], cand.ravel()[ok]] = True
            frontier = nxt & ~visited
            visited |= frontier
            d += 1
    return out


@dataclasses.dataclass
class TableDelta:
    """Changed rows + live masks from one :meth:`RoutingTables.apply_failures`.

    ``leaf_rows`` indexes the leaf-rank axis; the row arrays carry the
    recomputed distance/mask rows for exactly those leaves.  ``link_up``
    and ``switch_up`` are the *full* current liveness masks (tiny:
    ``N*P`` + ``N`` bools) — the engine consumes them wholesale.
    """

    leaf_rows: np.ndarray      # [K] int32 affected leaf ranks
    dist_rows: np.ndarray      # [K, N] int16 (UNREACHABLE where cut off)
    min_rows: np.ndarray       # [K, N, W] uint32 toward-bit rows
    away_rows: np.ndarray      # [K, N, W] uint32 away-bit rows
    link_up: np.ndarray        # [N, P] bool — directed-port liveness
    switch_up: np.ndarray      # [N] bool

    @property
    def n_affected(self) -> int:
        return int(self.leaf_rows.shape[0])


@dataclasses.dataclass
class RoutingTables:
    """Precomputed routing state shared by host router and simulator.

    ``dist_leaf`` stays int16 end to end (distances are tiny; the simulator
    gathers these rows on every crossbar sub-round, so half-width halves the
    memory traffic).  ``min_mask`` is the compact per-(target-leaf, switch)
    minimal-port bitmask: bit ``p`` of word ``min_mask[t, c, p // 32]`` is
    set iff port ``p`` of switch ``c`` leads one hop closer to leaf ``t``
    (``nbrs[c, p] >= 0 and dist_leaf[t, nbrs[c, p]] == dist_leaf[t, c] - 1``).
    Minimal policies (``minimal_adaptive``/``ksp``/``ugal``/``valiant``) test
    these bits instead of gathering whole ``[P]`` distance rows per packet.

    Two mask layouts exist (``mask_layout``):

    * ``"dense"``   — ``min_mask``/``away_mask`` hold the full
      ``[N1, N, W]`` uint32 arrays (small fabrics; host-side tooling).
    * ``"blocked"`` — the dense arrays are **never materialized**
      (``min_mask is None``); consumers stream ``leaf_block``-row leaf
      blocks through :meth:`mask_blocks` instead.  Peak host memory for
      the mask tables drops from ``2 * N1 * N * W * 4`` retained bytes to
      two transient ``leaf_block * N * W * 4``-byte blocks, which is what
      makes the paper's 100K-endpoint fabrics buildable on ordinary hosts
      (the simulator streams the blocks straight into its device tables).

    Either way the *values* are identical word for word — the blocked
    layout is a streaming order, not a different encoding — so simulator
    results are bitwise independent of the layout.
    """

    topo: Topology
    dist_leaf: np.ndarray          # [N1, N] int16 distances from each leaf
    leaf_rank: np.ndarray          # [N] rank among leaves or -1
    dist_full: Optional[np.ndarray] = None   # [N, N] (small nets / direct nets)
    min_mask: Optional[np.ndarray] = None    # [N1, N, W] uint32 toward-bits
    away_mask: Optional[np.ndarray] = None   # [N1, N, W] uint32 away-bits
    mask_layout: str = "dense"     # "dense" | "blocked"
    leaf_block: int = 256          # block height of the blocked layout
    dead_ports: Optional[np.ndarray] = None     # [N, P] bool, lazily allocated
    dead_switches: Optional[np.ndarray] = None  # [N] bool, lazily allocated

    @property
    def diameter_leaf(self) -> int:
        leaves = self.topo.leaf_ids
        return int(self.dist_leaf[:, leaves].max())

    @property
    def diameter_star(self) -> int:
        if self.dist_full is not None:
            return int(self.dist_full.max())
        return int(self.dist_leaf.max())       # max over (leaf, any-switch)

    @property
    def avg_distance_leaf(self) -> float:
        leaves = self.topo.leaf_ids
        d = self.dist_leaf[:, leaves].astype(np.float64)
        n1 = len(leaves)
        return float(d.sum() / (n1 * (n1 - 1)))

    def mask_blocks(self, block: Optional[int] = None):
        """Yield ``(lo, hi, min_block, away_block)`` leaf blocks.

        The one consumer-facing view of the port masks that works for both
        layouts: dense tables are sliced, blocked tables are computed on
        the fly from ``dist_leaf`` (one transient ``[block, N, W]`` pair at
        a time, never the dense array).  Blocks tile ``[0, N1)`` in order.
        """
        block = block or self.leaf_block
        if self.min_mask is not None and self.away_mask is not None:
            n1 = self.min_mask.shape[0]
            for lo in range(0, n1, block):
                hi = min(lo + block, n1)
                yield lo, hi, self.min_mask[lo:hi], self.away_mask[lo:hi]
            return
        yield from iter_port_mask_blocks(self.dist_leaf, self.topo.nbrs,
                                         block)

    # ------------------------------------------------------------------ #
    # delta rebuilds under failures
    # ------------------------------------------------------------------ #
    def apply_failures(self, down=(), up=()) -> TableDelta:
        """Apply link/switch state changes; recompute only affected rows.

        ``down``/``up`` are iterables of :class:`repro.core.failures
        .FailureEvent` taking effect now (``up`` restores previously
        downed elements).  The method mutates ``dist_leaf`` (and the
        dense ``min_mask``/``away_mask`` when materialized) **in place**
        — rows for unaffected leaves are untouched, and the dense
        ``[N1, N, W]`` tables are never re-materialized — then returns a
        :class:`TableDelta` with exactly the changed rows plus the full
        liveness masks.

        The frontier bound: a downed link ``{a, b}`` can change leaf
        ``t``'s distances only if the farther endpoint (say ``a``, with
        ``d(t,a) == d(t,b) + 1``) has **no other live toward port** —
        otherwise every shortest path re-routes through the alternate
        predecessor and all distances are preserved (both orientations
        are tested).  A restored link can change leaf ``t`` only if
        ``|d(t,a) - d(t,b)| >= 2`` on the current tables.  Switch events
        fall back to recomputing every leaf row (they cut up to ``P``
        links at once; the bench ladder uses link events only).

        Masks are always packed against the **static full adjacency**
        (``topo.nbrs``): a toward bit through a dead port stays set, and
        the engine's live up-mask excludes it at runtime.  That keeps
        :func:`_pack_mask_block` layout-identical for both mask layouts
        and makes restores nearly free — when a link comes back and no
        distance changed, the bits are already correct.
        """
        topo = self.topo
        n, p = topo.n_switches, topo.max_ports
        nbrs = topo.nbrs
        if self.dead_ports is None:
            self.dead_ports = np.zeros((n, p), bool)
            self.dead_switches = np.zeros(n, bool)
        n1 = self.dist_leaf.shape[0]
        affected = np.zeros(n1, bool)
        d32 = self.dist_leaf.astype(np.int32)          # sentinel-safe math

        # mark every down first, collecting freshly-killed link pairs; the
        # affected test then runs once, batched over all endpoints, against
        # the final dead state (a superset of the per-event sequential
        # test -- extra rows just recompute to identical values)
        down_pairs = []
        for ev in down:
            if ev.kind == "switch":
                self.dead_switches[ev.id] = True
                affected[:] = True
                continue
            c, pt = divmod(ev.id, p)
            nb = int(nbrs[c, pt])
            nbp = int(topo.nbr_port[c, pt])
            if not self.dead_ports[c, pt]:
                down_pairs.append((c, nb))
            self.dead_ports[c, pt] = True
            self.dead_ports[nb, nbp] = True
        if down_pairs and not affected.all():
            # x = farther endpoint candidates: both orientations of every
            # killed link; leaf t is affected iff d(t,x) == d(t,y) + 1 and
            # x keeps no other live toward port
            xs = sorted({x for pair in down_pairs for x in pair})
            xi = {x: i for i, x in enumerate(xs)}
            xa = np.asarray(xs)
            live = (nbrs[xa] >= 0) & ~self.dead_ports[xa]        # [X, P]
            nb_x = np.where(live, nbrs[xa], 0)
            alt = (live[None] & (d32[:, nb_x]
                                 == (d32[:, xa] - 1)[:, :, None])
                   ).any(axis=2)                                 # [N1, X]
            x2 = np.asarray([x for c, nb in down_pairs for x in (c, nb)])
            y2 = np.asarray([y for c, nb in down_pairs for y in (nb, c)])
            far = d32[:, x2] == d32[:, y2] + 1                   # [N1, 2K]
            cols = np.asarray([xi[x] for x in x2])
            affected |= (far & ~alt[:, cols]).any(axis=1)

        up_pairs = []
        for ev in up:
            if ev.kind == "switch":
                self.dead_switches[ev.id] = False
                affected[:] = True
                continue
            c, pt = divmod(ev.id, p)
            nb = int(nbrs[c, pt])
            nbp = int(topo.nbr_port[c, pt])
            if self.dead_ports[c, pt]:
                up_pairs.append((c, nb))
            self.dead_ports[c, pt] = False
            self.dead_ports[nb, nbp] = False
        if up_pairs and not affected.all():
            cs = np.asarray([c for c, _ in up_pairs])
            nbs = np.asarray([nb for _, nb in up_pairs])
            affected |= (np.abs(d32[:, cs] - d32[:, nbs]) >= 2).any(axis=1)

        valid = nbrs >= 0
        nbr_safe = np.where(valid, nbrs, 0)
        switch_up = ~self.dead_switches
        link_up = (valid & ~self.dead_ports
                   & switch_up[:, None] & switch_up[nbr_safe])

        leaf_rows = np.nonzero(affected)[0].astype(np.int32)
        k = len(leaf_rows)
        w = (p + 31) // 32
        if k == 0:
            return TableDelta(leaf_rows,
                              np.zeros((0, n), np.int16),
                              np.zeros((0, n, w), np.uint32),
                              np.zeros((0, n, w), np.uint32),
                              link_up, switch_up)

        # effective adjacency: dead ports and any port touching a dead
        # switch become -1 (BFS only; the topology itself never mutates)
        eff = nbrs.copy()
        eff[self.dead_ports] = -1
        eff[~switch_up] = -1
        eff[valid & ~switch_up[nbr_safe]] = -1
        newd = bfs_distances(topo, topo.leaf_ids[affected], nbrs=eff)
        dist_rows = np.where(newd < 0, UNREACHABLE, newd).astype(np.int16)
        self.dist_leaf[affected] = dist_rows

        min_rows = np.empty((k, n, w), np.uint32)
        away_rows = np.empty((k, n, w), np.uint32)
        for lo in range(0, k, self.leaf_block):        # bounded scratch
            hi = min(lo + self.leaf_block, k)
            min_rows[lo:hi], away_rows[lo:hi] = _pack_mask_block(
                dist_rows[lo:hi], nbrs, valid, nbr_safe)
        if self.min_mask is not None:
            self.min_mask[affected] = min_rows
            self.away_mask[affected] = away_rows
        return TableDelta(leaf_rows, dist_rows, min_rows, away_rows,
                          link_up, switch_up)


def _pack_mask_block(dist_block: np.ndarray, nbrs: np.ndarray,
                     valid: np.ndarray, nbr_safe: np.ndarray):
    """One ``(min, away)`` uint32 block [B, N, W] for a leaf slice.

    The single bit-packing implementation shared by the dense and blocked
    layouts — the layouts cannot drift apart because there is nothing to
    drift between.
    """
    p = nbrs.shape[1]
    w = (p + 31) // 32
    d = dist_block                                        # [B, N]
    dn = d[:, nbr_safe]                                   # [B, N, P]
    toward = valid[None] & (dn == (d[:, :, None] - 1))
    away = valid[None] & (dn == (d[:, :, None] + 1))
    # one shot bit-pack: port j contributes bit j%32 of word j//32; the
    # bits are distinct within a word, so the segmented sum IS the OR
    shifts = np.uint32(1) << (np.arange(p, dtype=np.uint32) % np.uint32(32))
    starts = np.arange(0, p, 32)
    min_b = np.add.reduceat(toward * shifts, starts, axis=2)
    away_b = np.add.reduceat(away * shifts, starts, axis=2)
    return min_b.astype(np.uint32, copy=False), \
        away_b.astype(np.uint32, copy=False)


def iter_port_mask_blocks(dist_leaf: np.ndarray, nbrs: np.ndarray,
                          block: int = 256):
    """Stream ``(lo, hi, min_block, away_block)`` leaf blocks.

    Each block is the ``[lo:hi]`` leaf slice of the dense
    :func:`pack_port_masks` output, computed without ever materializing
    the ``[N1, N, W]`` arrays — peak memory is one ``[block, N, P]``
    boolean intermediate plus the two ``[block, N, W]`` uint32 outputs.
    """
    n1 = dist_leaf.shape[0]
    valid = nbrs >= 0
    nbr_safe = np.where(valid, nbrs, 0)
    for lo in range(0, n1, block):
        hi = min(lo + block, n1)
        min_b, away_b = _pack_mask_block(dist_leaf[lo:hi], nbrs,
                                         valid, nbr_safe)
        yield lo, hi, min_b, away_b


def pack_port_masks(dist_leaf: np.ndarray, nbrs: np.ndarray,
                    leaf_chunk: int = 256):
    """``(min_mask, away_mask)`` — [N1, N, ceil(P/32)] uint32 bitmasks.

    Bit ``p`` of ``min_mask[t, c, p // 32]`` is set iff following port ``p``
    from switch ``c`` decreases the distance to leaf ``t`` by exactly one;
    ``away_mask`` is the increases-by-one twin.  Together they encode the
    full Polarized link classification (Forward / Expansion / Contraction
    are conjunctions of toward/away bits w.r.t. source and target, and the
    neighbor distance is recoverable as ``d(c,t) + away - toward``), so the
    simulator never gathers ``[P]``-wide distance rows.

    This is the *dense* assembly of :func:`iter_port_mask_blocks` — use
    the iterator directly (or ``build_tables(..., masks="blocked")``) when
    the ``2 * N1 * N * W * 4``-byte footprint matters.
    """
    n1, n = dist_leaf.shape
    p = nbrs.shape[1]
    w = (p + 31) // 32
    min_mask = np.zeros((n1, n, w), np.uint32)
    away_mask = np.zeros((n1, n, w), np.uint32)
    for lo, hi, min_b, away_b in iter_port_mask_blocks(dist_leaf, nbrs,
                                                       leaf_chunk):
        min_mask[lo:hi] = min_b
        away_mask[lo:hi] = away_b
    return min_mask, away_mask


def mask_table_bytes(n1: int, n: int, p: int) -> int:
    """Bytes of ONE dense ``[N1, N, W]`` uint32 mask table."""
    return n1 * n * ((p + 31) // 32) * 4


# ---------------------------------------------------------------------- #
# fused route rows: toward bits, away bits and distance in one row
# ---------------------------------------------------------------------- #
def route_row_words(p: int) -> int:
    """uint32 words of one fused route row for ``p`` ports.

    Bit layout of the row read as one little-endian bit string: toward
    bit of port ``j`` at bit ``j``, away bit of port ``j`` at bit
    ``p + j``, and the int16 distance in the top 16 bits of the last word.
    At radix 36 (``p = 36``) that is 88 bits in 3 words.
    """
    return (2 * p + 16 + 31) // 32


def _place_bits(out: np.ndarray, words: np.ndarray, offset: int) -> None:
    """OR the bit string ``words[..., :]`` into ``out`` at bit ``offset``.
    Bits of ``words`` past the field are zero, so nothing spills."""
    k = out.shape[-1]
    for j in range(words.shape[-1]):
        q, s = divmod(offset + 32 * j, 32)
        w = words[..., j]
        out[..., q] |= w << np.uint32(s)
        if s and q + 1 < k:
            out[..., q + 1] |= w >> np.uint32(32 - s)


def pack_route_rows(min_words: np.ndarray, away_words: np.ndarray,
                    dist: np.ndarray, p: int) -> np.ndarray:
    """``[..., K]`` uint32 fused route rows (:func:`route_row_words`).

    ``min_words``/``away_words`` are ``[..., W]`` toward/away bit words of
    the same rows (:func:`_pack_mask_block`), ``dist`` the ``[...]`` int16
    distances, exact (:data:`UNREACHABLE` included).  The one packing used
    by the simulator's table build and by its failure-delta updates.
    """
    k = route_row_words(p)
    out = np.zeros(dist.shape + (k,), np.uint32)
    _place_bits(out, min_words.astype(np.uint32, copy=False), 0)
    _place_bits(out, away_words.astype(np.uint32, copy=False), p)
    d16 = np.asarray(dist, np.int16).view(np.uint16).astype(np.uint32)
    out[..., k - 1] |= d16 << np.uint32(16)
    return out


def build_tables(topo: Topology, full: bool = False, *,
                 masks: str = "auto",
                 leaf_block: int = 256) -> RoutingTables:
    """Distance tables + packed port masks for ``topo``.

    ``masks`` picks the port-mask layout: ``"dense"`` materializes the
    ``[N1, N, W]`` numpy arrays, ``"blocked"`` defers them to streamed
    leaf blocks (:meth:`RoutingTables.mask_blocks`), and ``"auto"`` (the
    default) uses ``"blocked"`` once one dense table would exceed
    :data:`DENSE_MASK_LIMIT` bytes — so small fabrics keep the old
    behaviour exactly and paper-scale fabrics never hold dense masks.
    """
    if masks not in MASK_LAYOUTS:
        raise ValueError(f"unknown mask layout {masks!r}; expected one of "
                         f"{MASK_LAYOUTS}")
    with tracing.span("routing.tables"):
        dist_leaf = bfs_distances(topo, topo.leaf_ids)
        dist_full = (bfs_distances(topo, np.arange(topo.n_switches))
                     if full else None)
        if masks == "auto":
            dense_bytes = mask_table_bytes(topo.n_leaves, topo.n_switches,
                                           topo.max_ports)
            masks = "dense" if dense_bytes <= DENSE_MASK_LIMIT else "blocked"
        if masks == "dense":
            min_mask, away_mask = pack_port_masks(dist_leaf, topo.nbrs,
                                                  leaf_block)
        else:
            min_mask = away_mask = None
    return RoutingTables(topo, dist_leaf, topo.leaf_rank(), dist_full,
                         min_mask, away_mask, mask_layout=masks,
                         leaf_block=leaf_block)


# ---------------------------------------------------------------------- #
# Polarized port classification (numpy + jnp twins)
# ---------------------------------------------------------------------- #
def polarized_port_mask(
    d_cs, d_ct, d_ns, d_nt, hops, max_hops, valid,
):
    """Vectorized Polarized filter.  Works with numpy or jnp arrays.

    Args are broadcastable: ``d_cs, d_ct, hops`` per packet, ``d_ns, d_nt,
    valid`` per (packet, port).  Returns ``(allowed, is_deroute)`` masks.
    A deroute (Expansion/Contraction) additionally requires that the hop
    budget still admits finishing: ``hops + 1 + d_nt <= max_hops``.
    """
    import numpy as xp  # numpy semantics; jnp arrays pass through fine
    fwd = (d_ns == d_cs + 1) & (d_nt == d_ct - 1)
    exp_ = (d_ns == d_cs + 1) & (d_nt == d_ct + 1) & (d_cs < d_ct)
    con = (d_ns == d_cs - 1) & (d_nt == d_ct - 1) & (d_cs >= d_ct)
    budget_ok = (hops + 1 + d_nt) <= max_hops
    deroute = (exp_ | con)
    allowed = valid & (fwd | (deroute & budget_ok))
    del xp
    return allowed, deroute & valid


# ---------------------------------------------------------------------- #
# host-side reference router (tests, analytics, corner detection)
# ---------------------------------------------------------------------- #
def route_packet_host(
    tables: RoutingTables,
    src_leaf: int,
    dst_leaf: int,
    policy: str = "polarized",
    max_hops: Optional[int] = None,
    occupancy: Optional[np.ndarray] = None,     # [N, P] synthetic load
    rng: Optional[np.random.Generator] = None,
    deroute_penalty: float = 10.0,
) -> list[int]:
    """Route one packet switch-by-switch; returns the list of visited
    switches (including src and dst).  Raises RuntimeError on a *corner*
    (no allowed port — Section 4.3.2) or hop-budget exhaustion."""
    topo, dist = tables.topo, tables.dist_leaf
    lr = tables.leaf_rank
    s, t = lr[src_leaf], lr[dst_leaf]
    assert s >= 0 and t >= 0, "src/dst must be leaves"
    if max_hops is None:
        max_hops = 2 * tables.diameter_star - 2 if policy == "polarized" \
            else tables.diameter_leaf
    rng = rng or np.random.default_rng(0)
    occ = occupancy if occupancy is not None else np.zeros_like(topo.nbrs, np.float64)

    path = [src_leaf]
    cur, hops = src_leaf, 0
    mid = None
    if policy == "valiant" or policy == "ugal":
        mid = int(rng.choice(topo.leaf_ids))
        if policy == "ugal":       # UGAL-L: pick VAL only if MIN looks congested
            min_ports = np.nonzero(
                (topo.nbrs[cur] >= 0)
                & (dist[t, topo.nbrs[cur]] == dist[t, cur] - 1))[0]
            val_ports = np.nonzero(
                (topo.nbrs[cur] >= 0)
                & (dist[lr[mid], topo.nbrs[cur]] == dist[lr[mid], cur] - 1))[0]
            q_min = occ[cur, min_ports].min() if min_ports.size else np.inf
            q_val = occ[cur, val_ports].min() if val_ports.size else np.inf
            d_min, d_val = dist[t, cur], dist[lr[mid], cur] + dist[t, mid]
            if q_min * d_min <= q_val * d_val:
                mid = None        # go minimal
    target_rank = t if mid is None else lr[mid]

    while cur != dst_leaf:
        if hops >= max_hops:
            raise RuntimeError(f"hop budget exhausted at {cur} ({policy})")
        nb = topo.nbrs[cur]
        valid = nb >= 0
        nb_safe = np.where(valid, nb, 0)
        if policy == "polarized":
            allowed, deroute = polarized_port_mask(
                dist[s, cur], dist[t, cur],
                dist[s, nb_safe], dist[t, nb_safe],
                hops, max_hops, valid)
            if not allowed.any():
                raise RuntimeError(f"corner at switch {cur} for pair ({src_leaf},{dst_leaf})")
            score = occ[cur] + deroute_penalty * deroute + rng.uniform(0, 1e-6, nb.shape)
            score = np.where(allowed, score, np.inf)
            port = int(np.argmin(score))
        else:
            # minimal (adaptive / random) toward current target
            min_mask = valid & (dist[target_rank, nb_safe] == dist[target_rank, cur] - 1)
            if not min_mask.any():
                raise RuntimeError(f"no minimal port at {cur}")
            ports = np.nonzero(min_mask)[0]
            if policy == "ksp":
                port = int(rng.choice(ports))      # randomized minimal-DAG walk
            else:                                  # minimal_adaptive / ugal / valiant
                port = int(ports[np.argmin(occ[cur, ports])])
        cur = int(topo.nbrs[cur, port])
        hops += 1
        path.append(cur)
        if mid is not None and cur == mid:
            mid = None
            target_rank = t
    return path


def find_corners(tables: RoutingTables, n_samples: int = 2000, seed: int = 0) -> int:
    """Sample (s, t) leaf pairs and count Polarized routing failures
    (corners).  The paper re-rolls the MRLS if any corner exists; for random
    topologies the probability is negligible (Section 4.3.2)."""
    rng = np.random.default_rng(seed)
    leaves = tables.topo.leaf_ids
    corners = 0
    for _ in range(n_samples):
        a, b = rng.choice(leaves, 2, replace=False)
        try:
            route_packet_host(tables, int(a), int(b), "polarized", rng=rng)
        except RuntimeError:
            corners += 1
    return corners
