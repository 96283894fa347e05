"""Deterministic equivalence + invariant tests for the packed-bitset
conflict-graph engine and the multi-seed SBTS portfolio (no hypothesis
dependency: every case is seeded and enumerated)."""

import numpy as np
import pytest

from repro.core import (BitsetGraph, make_cnkm, map_dfg, schedule_dfg,
                        solve_mis, solve_mis_portfolio)
from repro.core.bitset import (as_bitset_graph, indices, pack_bool,
                               pack_indices, popcount, unpack)
from repro.core.cgra import CGRAConfig
from repro.core.conflict import (_dep_ok, bitset_group_conflicts,
                                 build_conflict_graph, constructive_init,
                                 dense_conflicts_python)
from repro.core.mis import PortfolioSBTS, greedy_mis

CGRA = CGRAConfig()


def _random_adj(n, density, seed):
    rng = np.random.default_rng(seed)
    adj = rng.random((n, n)) < density
    adj = np.triu(adj, 1)
    return adj | adj.T


# ------------------------------------------------------------ primitives
@pytest.mark.parametrize("n", [1, 5, 63, 64, 65, 200, 513])
def test_pack_unpack_roundtrip(n):
    rng = np.random.default_rng(n)
    mask = rng.random(n) < 0.3
    words = pack_bool(mask)
    assert words.size == (n + 63) // 64
    np.testing.assert_array_equal(unpack(words, n).astype(bool), mask)
    assert popcount(words) == int(mask.sum())
    np.testing.assert_array_equal(indices(words, n), np.flatnonzero(mask))
    idx = np.flatnonzero(mask)
    np.testing.assert_array_equal(pack_indices(idx, n), words)


@pytest.mark.parametrize("n,density,seed",
                         [(7, 0.5, 0), (64, 0.2, 1), (130, 0.1, 2),
                          (301, 0.35, 3)])
def test_bitset_graph_dense_roundtrip(n, density, seed):
    adj = _random_adj(n, density, seed)
    g = BitsetGraph.from_dense(adj)
    np.testing.assert_array_equal(g.to_dense(), adj)
    assert g.n_edges == int(adj.sum()) // 2
    np.testing.assert_array_equal(g.degrees(), adj.sum(axis=1))
    s = np.zeros(n, dtype=bool)
    s[::3] = True
    np.testing.assert_array_equal(g.conflict_counts(pack_bool(s)),
                                  adj[:, s].sum(axis=1))


def test_bitset_graph_add_edges_matches_dense():
    n = 97
    rng = np.random.default_rng(4)
    i = rng.integers(0, n, 300)
    j = rng.integers(0, n, 300)
    g = BitsetGraph(n)
    g.add_edges(i, j)
    dense = np.zeros((n, n), dtype=bool)
    for a, b in zip(i, j):
        if a != b:
            dense[a, b] = dense[b, a] = True
    np.testing.assert_array_equal(g.to_dense(), dense)


# -------------------------------------------------- conflict-graph build
@pytest.mark.parametrize("n,m,mode", [(1, 2, "bandmap"), (2, 6, "bandmap"),
                                      (3, 6, "busmap"), (4, 4, "bandmap"),
                                      (2, 8, "busmap"), (5, 5, "busmap")])
def test_group_conflicts_byte_identical_to_oracle(n, m, mode):
    """bitset group rules == dense_conflicts_python, bit for bit."""
    sched = schedule_dfg(make_cnkm(n, m), CGRA, mode=mode)
    cg = build_conflict_graph(sched, CGRA)
    bits = bitset_group_conflicts(cg.vertices, cg.op_vertices, sched.ii)
    oracle = dense_conflicts_python(cg.vertices, cg.op_vertices, sched.ii)
    np.testing.assert_array_equal(bits.to_dense(), oracle)


@pytest.mark.parametrize("n,m,mode", [(2, 6, "bandmap"), (3, 6, "busmap"),
                                      (5, 5, "busmap")])
def test_full_adjacency_equals_seed_reference(n, m, mode):
    """Full build (groups + vectorised dep realizability) == the seed
    engine's formulation (oracle groups + python _dep_ok loop)."""
    sched = schedule_dfg(make_cnkm(n, m), CGRA, mode=mode)
    cg = build_conflict_graph(sched, CGRA)
    ref = dense_conflicts_python(cg.vertices, cg.op_vertices, sched.ii)
    for src, dst in {(e.src, e.dst) for e in sched.dfg.edges}:
        for i in cg.op_vertices[src]:
            for j in cg.op_vertices[dst]:
                if not _dep_ok(cg.vertices[i], cg.vertices[j]):
                    ref[i, j] = ref[j, i] = True
    np.testing.assert_array_equal(cg.bits.to_dense(), ref)
    assert cg.n_edges == int(ref.sum()) // 2


def test_adjacency_identical_on_8x8_cgra():
    big = CGRAConfig(rows=8, cols=8)
    sched = schedule_dfg(make_cnkm(3, 6), big)
    cg = build_conflict_graph(sched, big)
    assert cg.n > 1000          # the scenario the dense path can't reach
    ref = dense_conflicts_python(cg.vertices, cg.op_vertices, sched.ii)
    for src, dst in {(e.src, e.dst) for e in sched.dfg.edges}:
        for i in cg.op_vertices[src]:
            for j in cg.op_vertices[dst]:
                if not _dep_ok(cg.vertices[i], cg.vertices[j]):
                    ref[i, j] = ref[j, i] = True
    np.testing.assert_array_equal(cg.bits.to_dense(), ref)


# ------------------------------------------------------------- portfolio
@pytest.mark.parametrize("seed", range(6))
def test_portfolio_independence_random_graphs(seed):
    """Every per-seed best of the portfolio is an independent set."""
    n = 40 + 17 * seed
    adj = _random_adj(n, 0.08 + 0.06 * seed, seed)
    inits = [None, None, greedy_mis(adj, np.random.default_rng(seed)),
             None]
    bests = solve_mis_portfolio(adj, inits=inits, max_iters=400, seed=seed)
    assert bests.shape == (4, n)
    for row in bests:
        idx = np.flatnonzero(row)
        assert not adj[np.ix_(idx, idx)].any()


@pytest.mark.parametrize("seed", range(4))
def test_portfolio_dominates_single_seed(seed):
    """The portfolio's best is never worse than its own member
    trajectories run alone with the same seed stream."""
    adj = _random_adj(80, 0.15, seed + 100)
    single = solve_mis(adj, max_iters=300, seed=seed)
    bests = solve_mis_portfolio(adj, inits=[None] * 4 + [single],
                                max_iters=300, seed=seed)
    assert int(bests.sum(axis=1).max()) >= int(single.sum())


@pytest.mark.parametrize("n,m", [(1, 2), (2, 4), (4, 4)])
def test_portfolio_reaches_target_on_cnkm(n, m):
    """Size parity with the seed solver: on the easy bandmap instances
    both the single-seed solver and the portfolio cover every op."""
    sched = schedule_dfg(make_cnkm(n, m), CGRA, mode="bandmap")
    cg = build_conflict_graph(sched, CGRA)
    n_ops = len(sched.dfg.ops)
    init = constructive_init(cg, sched, CGRA, seed=0)
    single = solve_mis(cg.bits, target=n_ops, max_iters=4000, seed=0,
                       init=init)
    bests = solve_mis_portfolio(cg.bits, inits=[init, None, None],
                                target=n_ops, max_iters=4000, seed=0)
    assert int(single.sum()) == n_ops
    assert int(bests.sum(axis=1).max()) == n_ops


def test_rearm_and_reset_preserve_invariants():
    adj = _random_adj(60, 0.2, 7)
    g = as_bitset_graph(adj)
    sbts = PortfolioSBTS(g, [None, None], seed=3)
    sbts.run(200)
    for k in range(2):
        sbts.rearm(k)
        np.testing.assert_array_equal(
            sbts.conf[k], g.conflict_counts(pack_bool(sbts.in_s[k])))
        idx = np.flatnonzero(sbts.in_s[k])
        assert not adj[np.ix_(idx, idx)].any()
    sbts.reset_seed(0)
    np.testing.assert_array_equal(
        sbts.conf[0], g.conflict_counts(pack_bool(sbts.in_s[0])))
    sbts.run(100)
    for row in sbts.best:
        idx = np.flatnonzero(row)
        assert not adj[np.ix_(idx, idx)].any()


# ------------------------------------------------------------ end-to-end
def test_map_completes_on_8x8_cgra():
    """The new scenario: an 8x8 PEA maps end-to-end, fast."""
    big = CGRAConfig(rows=8, cols=8)
    r = map_dfg(make_cnkm(3, 6), big, mode="bandmap")
    assert r.ok and r.ii == r.mii == 1
    assert r.cg_size[0] > 1000
    r2 = map_dfg(make_cnkm(4, 8), big, mode="busmap")
    assert r2.ok and r2.ii == 1
    assert r2.cg_size[0] > 2000


# ----------------------------------------------- row-cache configurability
def test_row_cache_limit_fallback_equivalence():
    """PortfolioSBTS trajectories are bit-identical whether rows come
    from the unpacked u8 cache or the per-move-unpack fallback — the
    cap (now configurable) only trades memory for gather speed."""
    sched = schedule_dfg(make_cnkm(3, 6), CGRAConfig())
    cg = build_conflict_graph(sched, CGRAConfig())
    n_ops = len(sched.dfg.ops)
    runs = []
    for limit in (None, 0):          # default cache vs forced fallback
        sbts = PortfolioSBTS(cg.bits, [None] * 4, seed=7,
                             row_cache_limit=limit)
        assert (sbts._u8 is None) == (limit == 0)
        runs.append(sbts.run(300, target=n_ops).copy())
    assert (runs[0] == runs[1]).all()


def test_row_cache_limit_threads_through_map_dfg():
    r_cached = map_dfg(make_cnkm(2, 6), CGRAConfig(), mode="busmap")
    r_fallback = map_dfg(make_cnkm(2, 6), CGRAConfig(), mode="busmap",
                         row_cache_limit=0)
    assert (r_cached.ok, r_cached.ii, r_cached.n_routing_pes) == \
        (r_fallback.ok, r_fallback.ii, r_fallback.n_routing_pes)


@pytest.mark.slow
def test_row_cache_fallback_hit_at_16x16_scale():
    """|V_C| ~ 10^4 (a 40-op generated kernel on a 16x16 PEA) exceeds
    the default 32 MiB bound: the constructor must skip the cache and
    the per-move fallback must still solve."""
    from repro.core import scale_16x16_loop
    from repro.core.mis import ROW_CACHE_LIMIT
    big = CGRAConfig(rows=16, cols=16)
    sched = schedule_dfg(scale_16x16_loop(), big, max_bus_fanout=4)
    cg = build_conflict_graph(sched, big)
    assert cg.n > 10_000
    assert cg.n * cg.n > ROW_CACHE_LIMIT
    sbts = PortfolioSBTS(cg.bits, [None] * 2, seed=0)
    assert sbts._u8 is None                      # fallback hit
    bests = sbts.run(150, target=len(sched.dfg.ops))
    for row in bests:                            # independence held
        assert not cg.bits.any_conflict(pack_bool(row))
