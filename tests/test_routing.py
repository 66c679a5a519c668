"""Routing: BFS correctness, Polarized Theorem 4.2 bound, deroutes."""
import networkx as nx
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (mrls, oft, fat_tree, build_tables, bfs_distances,
                        route_packet_host, find_corners)


def _to_nx(topo):
    g = nx.Graph()
    g.add_nodes_from(range(topo.n_switches))
    c, p = np.nonzero(topo.nbrs >= 0)
    for a, b in zip(c, topo.nbrs[c, p]):
        g.add_edge(int(a), int(b))
    return g


def test_bfs_matches_networkx():
    t = mrls(30, u=4, d=4, seed=3)
    g = _to_nx(t)
    dist = bfs_distances(t, t.leaf_ids)
    for i, src in enumerate(t.leaf_ids[:6]):
        ref = nx.single_source_shortest_path_length(g, int(src))
        for node, d in ref.items():
            assert dist[i, node] == d


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 30))
def test_polarized_bound_theorem_4_2(seed):
    """Route length <= 2 D* - 2 (Theorem 4.2) and no corners."""
    t = mrls(40, u=5, d=5, seed=seed)
    tb = build_tables(t, full=True)
    bound = 2 * tb.diameter_star - 2
    rng = np.random.default_rng(seed)
    leaves = t.leaf_ids
    for _ in range(30):
        a, b = rng.choice(leaves, 2, replace=False)
        path = route_packet_host(tb, int(a), int(b), "polarized",
                                 max_hops=bound, rng=rng)
        assert len(path) - 1 <= bound
        assert path[0] == a and path[-1] == b


def test_no_corners_on_paper_mrls():
    t = mrls(614, u=18, d=18, seed=1)
    tb = build_tables(t)
    assert find_corners(tb, n_samples=300) == 0


def test_polarized_routes_alternate_updown():
    """Routes follow the [Up-Down]* structure of Section 4.3."""
    t = mrls(40, u=5, d=5, seed=0)
    tb = build_tables(t)
    rng = np.random.default_rng(0)
    for _ in range(20):
        a, b = rng.choice(t.leaf_ids, 2, replace=False)
        path = route_packet_host(tb, int(a), int(b), "polarized", rng=rng)
        levels = [int(t.level[s]) for s in path]
        assert levels[0] == 0 and levels[-1] == 0
        for x, y in zip(levels, levels[1:]):
            assert x != y                 # bipartite: always level change


def test_polarized_deroutes_around_congestion():
    t = oft(5)
    tb = build_tables(t)
    rng = np.random.default_rng(0)
    p0 = route_packet_host(tb, 0, 7, "polarized", max_hops=6, rng=rng)
    assert len(p0) - 1 == 2               # minimal through the shared spine
    occ = np.zeros_like(t.nbrs, float)
    occ[0, list(t.nbrs[0]).index(p0[1])] = 100.0
    p1 = route_packet_host(tb, 0, 7, "polarized", max_hops=6,
                           occupancy=occ, rng=rng)
    assert len(p1) - 1 == 4               # expansion + contraction deroute
    assert p1[1] != p0[1]


def test_minimal_adaptive_on_fat_tree():
    t = fat_tree(8, 2)
    tb = build_tables(t)
    rng = np.random.default_rng(1)
    for _ in range(20):
        a, b = rng.choice(t.leaf_ids, 2, replace=False)
        path = route_packet_host(tb, int(a), int(b), "minimal_adaptive",
                                 rng=rng)
        assert len(path) - 1 == tb.dist_leaf[tb.leaf_rank[a], b]


def test_ksp_randomizes_paths():
    t = mrls(60, u=6, d=6, seed=2)
    tb = build_tables(t)
    rng = np.random.default_rng(0)
    total_paths, pairs = 0, 0
    for i in range(10):
        a, b = (int(x) for x in rng.choice(t.leaf_ids, 2, replace=False))
        paths = {tuple(route_packet_host(tb, a, b, "ksp", rng=rng))
                 for _ in range(12)}
        total_paths += len(paths)
        pairs += 1
    assert total_paths > pairs            # randomization across equal paths


# ---------------------------------------------------------------------- #
# leaf-blocked mask layout (ISSUE 5): blocked == dense, always
# ---------------------------------------------------------------------- #
@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 1000),
       n_leaves=st.sampled_from([12, 14, 20, 30]),
       u=st.integers(2, 5),
       block=st.integers(1, 40))
def test_blocked_mask_blocks_tile_dense(seed, n_leaves, u, block):
    """Streamed leaf blocks tile the dense tables exactly: same values,
    full disjoint coverage, any block size."""
    from repro.core import build_tables, mrls

    t = mrls(n_leaves, u=u, d=u, seed=seed)
    dense = build_tables(t, masks="dense")
    blocked = build_tables(t, masks="blocked", leaf_block=block)
    assert dense.mask_layout == "dense" and dense.min_mask is not None
    assert blocked.mask_layout == "blocked" and blocked.min_mask is None
    covered = np.zeros(t.n_leaves, bool)
    for lo, hi, min_b, away_b in blocked.mask_blocks():
        assert 0 <= lo < hi <= t.n_leaves
        assert not covered[lo:hi].any()          # disjoint
        covered[lo:hi] = True
        np.testing.assert_array_equal(min_b, dense.min_mask[lo:hi])
        np.testing.assert_array_equal(away_b, dense.away_mask[lo:hi])
    assert covered.all()                         # complete


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000), block=st.integers(1, 17))
def test_blocked_gather_matches_dense_gather(seed, block):
    """The engine-style flat assembly of streamed blocks gathers the same
    words as indexing the dense [N1, N, W] arrays, and every unpacked bit
    agrees with the distance predicate it encodes."""
    from repro.core import build_tables, mrls

    t = mrls(16, u=3, d=3, seed=seed)
    n1, n, p = t.n_leaves, t.n_switches, t.max_ports
    dense = build_tables(t, masks="dense")
    blocked = build_tables(t, masks="blocked", leaf_block=block)
    w = dense.min_mask.shape[-1]
    flat = {
        "min": np.concatenate([b.reshape(-1, w)
                               for _, _, b, _ in blocked.mask_blocks()]),
        "away": np.concatenate([b.reshape(-1, w)
                                for _, _, _, b in blocked.mask_blocks()]),
    }
    np.testing.assert_array_equal(flat["min"], dense.min_mask.reshape(-1, w))
    np.testing.assert_array_equal(flat["away"],
                                  dense.away_mask.reshape(-1, w))
    rng = np.random.default_rng(seed)
    dist = dense.dist_leaf
    for _ in range(50):
        tl, c = int(rng.integers(n1)), int(rng.integers(n))
        words = flat["min"][tl * n + c]
        bits = (words[np.arange(p) // 32] >> (np.arange(p) % 32)) & 1
        nbr = t.nbrs[c]
        toward = (nbr >= 0) & (dist[tl, np.maximum(nbr, 0)]
                               == dist[tl, c] - 1)
        np.testing.assert_array_equal(bits.astype(bool), toward)


def test_build_tables_auto_layout_threshold(monkeypatch):
    """"auto" resolves to dense below DENSE_MASK_LIMIT and blocked above
    it (forced low here so a tiny fabric crosses the line)."""
    from repro.core import build_tables, mrls
    from repro.core import routing as routing_mod

    t = mrls(14, u=3, d=3, seed=0)
    assert build_tables(t).mask_layout == "dense"
    monkeypatch.setattr(routing_mod, "DENSE_MASK_LIMIT", 64)
    assert build_tables(t).mask_layout == "blocked"
    with pytest.raises(ValueError, match="mask layout"):
        build_tables(t, masks="sparse")


# ---------------------------------------------------------------------- #
# fused route rows: toward bits, away bits and distance in one row
# ---------------------------------------------------------------------- #
def unpack_route_rows(rows, p):
    """``(min_words, away_words, dist)`` of fused rows, decoded bit by bit
    on the host: the reference the packing is held to."""
    w = (p + 31) // 32
    bits = np.unpackbits(rows.astype("<u4").view(np.uint8), axis=-1,
                         bitorder="little")
    pad = np.zeros(bits.shape[:-1] + (32 * w - p,), np.uint8)

    def words(field):
        b = np.concatenate([field, pad], axis=-1)
        return np.packbits(b, axis=-1, bitorder="little").view("<u4") \
            .astype(np.uint32)

    top = bits[..., 32 * rows.shape[-1] - 16:]
    dist = np.packbits(top, axis=-1, bitorder="little").view("<i2")[..., 0]
    return words(bits[..., :p]), words(bits[..., p:2 * p]), dist


@pytest.mark.parametrize("masks,block", [("dense", 256), ("blocked", 1),
                                         ("blocked", 5), ("blocked", 256)])
def test_route_rows_round_trip_of_the_tables(masks, block):
    """Packed block by block in either layout, the fused rows unpack to the
    dense toward words, away words and int16 distances; at radix 36 a row
    is 3 words and the away field straddles words 1 and 2."""
    from repro.core import (build_tables, mrls, pack_route_rows,
                            route_row_words)

    t = mrls(24, u=18, d=18, seed=0)
    p = t.max_ports
    assert p == 36 and route_row_words(p) == 3
    dense = build_tables(t, masks="dense")
    tb = build_tables(t, masks=masks, leaf_block=block)
    covered = 0
    for lo, hi, min_b, away_b in tb.mask_blocks():
        rows = pack_route_rows(min_b, away_b, tb.dist_leaf[lo:hi], p)
        assert rows.shape == (hi - lo, t.n_switches, 3)
        m, a, d = unpack_route_rows(rows, p)
        np.testing.assert_array_equal(m, dense.min_mask[lo:hi])
        np.testing.assert_array_equal(a, dense.away_mask[lo:hi])
        np.testing.assert_array_equal(d, dense.dist_leaf[lo:hi])
        assert d.dtype == np.int16
        covered += hi - lo
    assert covered == t.n_leaves


@pytest.mark.parametrize("p", [1, 6, 31, 32, 36, 48, 64])
def test_route_rows_round_trip_of_any_width(p):
    """Every port count: random bit words and int16 distances (the
    UNREACHABLE sentinel and negatives included) survive the packing."""
    from repro.core import UNREACHABLE, pack_route_rows, route_row_words

    rng = np.random.default_rng(p)
    w = (p + 31) // 32
    top = np.uint32((1 << (p - 32 * (w - 1))) - 1)    # ports past p are 0

    def words():
        x = rng.integers(0, 1 << 32, (40, w), dtype=np.uint64).astype(
            np.uint32)
        x[:, -1] &= top
        return x

    mw, aw = words(), words()
    dist = rng.integers(-(1 << 15), 1 << 15, 40).astype(np.int16)
    dist[:3] = (UNREACHABLE, 0, -1)
    rows = pack_route_rows(mw, aw, dist, p)
    assert rows.shape == (40, route_row_words(p))
    assert 32 * rows.shape[1] >= 2 * p + 16
    m, a, d = unpack_route_rows(rows, p)
    np.testing.assert_array_equal(m, mw)
    np.testing.assert_array_equal(a, aw)
    np.testing.assert_array_equal(d, dist)


def test_route_rows_round_trip_of_a_delta_with_unreachable_rows():
    """A failed leaf switch cuts every leaf off from it: the delta's rows
    carry UNREACHABLE, and their fused rows keep it exactly."""
    from repro.core import (UNREACHABLE, FailureEvent, build_tables, mrls,
                            pack_route_rows)

    t = mrls(24, u=18, d=18, seed=0)
    tb = build_tables(t, masks="blocked", leaf_block=7)
    delta = tb.apply_failures(down=(FailureEvent("switch",
                                                 int(t.leaf_ids[3]), 0),))
    assert delta.n_affected == t.n_leaves
    assert (delta.dist_rows == UNREACHABLE).any()
    rows = pack_route_rows(delta.min_rows, delta.away_rows, delta.dist_rows,
                           t.max_ports)
    m, a, d = unpack_route_rows(rows, t.max_ports)
    np.testing.assert_array_equal(m, delta.min_rows)
    np.testing.assert_array_equal(a, delta.away_rows)
    np.testing.assert_array_equal(d, delta.dist_rows)
