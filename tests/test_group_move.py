"""Group-move ("kick") neighbourhood of the SBTS portfolio: flag-off
bit-identity, cluster-move validity invariants, and the end-to-end
tightly-coupled regression the neighbourhood exists for (a VIO whose
bus-fed consumers span rows is unreachable for (1,1) swaps)."""

import numpy as np
import pytest

from repro.core import (GroupMoveConfig, make_cnkm, make_tightly_coupled,
                        map_dfg, schedule_dfg)
from repro.core.bitset import BitsetGraph, pack_bool
from repro.core.cgra import CGRAConfig
from repro.core.conflict import build_conflict_graph
from repro.core.mis import PortfolioSBTS

CGRA = CGRAConfig()
BIG = CGRAConfig(rows=8, cols=8)


def _tight_cg(ii=2):
    d = make_tightly_coupled(8, 8, 2, link_run=6, seed=0)
    sched = schedule_dfg(d, BIG, ii=ii, max_ii=ii)
    cg = build_conflict_graph(sched, BIG, bus_pressure=True)
    return cg, sched, cg.op_of


# ----------------------------------------------------- bitset primitives
def _random_adj(n, density, seed):
    rng = np.random.default_rng(seed)
    adj = rng.random((n, n)) < density
    adj = np.triu(adj, 1)
    return adj | adj.T


@pytest.mark.parametrize("n,seed", [(67, 0), (130, 1), (300, 2)])
def test_union_rows_and_cluster_members_vs_bruteforce(n, seed):
    adj = _random_adj(n, 0.1, seed)
    g = BitsetGraph.from_dense(adj)
    rng = np.random.default_rng(seed + 10)
    vs = rng.choice(n, size=7, replace=False)
    union_ref = adj[vs].any(axis=0)
    from repro.core.bitset import unpack
    np.testing.assert_array_equal(
        unpack(g.union_rows(vs), n).astype(bool), union_ref)
    s = rng.random(n) < 0.3
    ref = np.flatnonzero(union_ref & s)
    np.testing.assert_array_equal(g.cluster_members(vs, pack_bool(s)), ref)


def test_union_rows_empty():
    g = BitsetGraph(65)
    assert g.union_rows(np.asarray([], dtype=np.int64)).sum() == 0
    assert g.cluster_members([], pack_bool(np.ones(65, bool))).size == 0


# ------------------------------------------------- flag-off bit-identity
@pytest.mark.parametrize("n,m,mode", [(2, 6, "bandmap"), (3, 6, "busmap"),
                                      (5, 5, "busmap")])
def test_flag_off_bit_identical_on_paper_kernels(n, m, mode):
    """Constructing the portfolio with op_of (as map_dfg now always
    does) and group_move disabled must leave every trajectory
    bit-identical to the plain portfolio."""
    sched = schedule_dfg(make_cnkm(n, m), CGRA, mode=mode)
    cg = build_conflict_graph(sched, CGRA)
    op_of = cg.op_of
    n_ops = len(sched.dfg.ops)
    runs = []
    for kw in ({}, dict(op_of=op_of),
               dict(op_of=op_of, group_move=GroupMoveConfig(enabled=False))):
        sbts = PortfolioSBTS(cg.bits, [None] * 4, seed=11, **kw)
        runs.append(sbts.run(500, target=n_ops).copy())
        assert sbts._gm is None
    assert (runs[0] == runs[1]).all()
    assert (runs[0] == runs[2]).all()


def test_flag_off_rearm_bit_identical():
    adj = _random_adj(80, 0.15, 3)
    g = BitsetGraph.from_dense(adj)
    op_of = np.arange(80) // 4
    states = []
    for kw in ({}, dict(op_of=op_of)):
        sbts = PortfolioSBTS(g, [None, None], seed=5, **kw)
        sbts.run(200)
        sbts.rearm(0)
        sbts.reset_seed(1)
        sbts.run(100)
        states.append((sbts.best.copy(), sbts.in_s.copy(),
                       sbts.tabu.copy()))
    for a, b in zip(states[0], states[1]):
        assert (a == b).all()


def test_group_move_requires_op_of():
    g = BitsetGraph.from_dense(_random_adj(10, 0.3, 0))
    with pytest.raises(ValueError):
        PortfolioSBTS(g, [None], group_move=GroupMoveConfig())


# ------------------------------------------------- kick-phase invariants
def test_kick_preserves_independence_and_conf():
    """After kick phases: membership stays an independent set, conf is
    the exact conflict count, and size bookkeeping matches."""
    cg, sched, op_of = _tight_cg()
    n_ops = len(sched.dfg.ops)
    gm = GroupMoveConfig(cadence=20)
    sbts = PortfolioSBTS(cg.bits, [None] * 3, seed=2, op_of=op_of,
                         group_move=gm)
    for _ in range(6):
        sbts.run(60, target=n_ops)
        for k in range(3):
            row = sbts.in_s[k]
            assert not cg.bits.any_conflict(pack_bool(row))
            np.testing.assert_array_equal(
                cg.bits.conflict_counts(pack_bool(row)), sbts.conf[k])
            assert int(row.sum()) == int(sbts.size[k])
        if (sbts.best_size >= n_ops).any():
            break


def test_kick_respects_tabu():
    """A vertex ejected by the kick is tabu for the kick's tenure: the
    kick itself never re-inserts it while tabu (the re-insertion filter
    is `tabu <= it`)."""
    cg, sched, op_of = _tight_cg()
    n_ops = len(sched.dfg.ops)
    gm = GroupMoveConfig(cadence=10, tenure=50)
    sbts = PortfolioSBTS(cg.bits, [None] * 2, seed=0, op_of=op_of,
                         group_move=gm)
    sbts.run(400, target=n_ops)   # reach the stall, several kicks fire
    before = sbts.in_s.copy()
    sbts.it += 1
    sbts.stall[:] = gm.cadence    # open the stall gate
    sbts._group_kick(n_ops)
    for k in range(2):
        ejected = np.flatnonzero(before[k] & ~sbts.in_s[k])
        if ejected.size:
            assert (sbts.tabu[k, ejected] > sbts.it).all()
        inserted = np.flatnonzero(~before[k] & sbts.in_s[k])
        # nothing inserted was tabu at kick time
        assert (sbts.tabu[k, inserted] <= sbts.it).all() or \
            not inserted.size


def test_rearm_cluster_eviction_invariants():
    """With the flag on, rearm evicts a coherent cluster, keeps the
    state independent, and re-arms best tracking."""
    cg, sched, op_of = _tight_cg()
    n_ops = len(sched.dfg.ops)
    sbts = PortfolioSBTS(cg.bits, [None] * 2, seed=1, op_of=op_of,
                         group_move=GroupMoveConfig())
    sbts.run(300, target=n_ops)
    for k in range(2):
        sbts.rearm(k)
        row = sbts.in_s[k]
        assert not cg.bits.any_conflict(pack_bool(row))
        np.testing.assert_array_equal(
            cg.bits.conflict_counts(pack_bool(row)), sbts.conf[k])
        assert sbts.best_size[k] == int(row.sum())


# ------------------------------------------------ the stall, end to end
def test_tight_workload_engine_stalls_without_kick():
    """Cold-started portfolios on the tightly-coupled family: the
    (1,1)-swap engine stalls below full coverage at the iteration
    budget; the kick-enabled engine reaches it — same graph, same
    budget, same seeds."""
    cg, sched, op_of = _tight_cg()
    n_ops = len(sched.dfg.ops)
    for seed in (0, 1):
        off = PortfolioSBTS(cg.bits, [None] * 8, seed=seed, op_of=op_of)
        best_off = int(off.run(3000, target=n_ops).sum(axis=1).max())
        on = PortfolioSBTS(cg.bits, [None] * 8, seed=seed, op_of=op_of,
                           group_move=GroupMoveConfig())
        best_on = int(on.run(3000, target=n_ops).sum(axis=1).max())
        assert best_off < n_ops, "swap engine no longer stalls"
        assert best_on == n_ops, "kick engine failed to cover"
        assert on.it < 3000, "kick engine should early-exit"


def test_tight_workload_map_dfg_flag_on_vs_off():
    """End to end: at equal iteration budget and pinned II, `map_dfg`
    with group_move enabled produces a *valid* full-coverage binding
    the flag-off engine does not reach (the acceptance scenario).  The
    graph carries no capacity edges: with them the LRF-pressure edges
    rule out the placements the flag-off engine is rejected on here,
    and it binds too."""
    d = make_tightly_coupled(8, 8, 2, link_run=6, seed=0)
    kw = dict(certify=False, mis_restarts=4, mis_iters=2500,
              min_ii=2, max_ii=2, seed=0, bus_pressure=False)
    r_off = map_dfg(d, BIG, **kw)
    r_on = map_dfg(d, BIG, group_move=True, **kw)
    assert not r_off.ok and r_off.mis_size < r_off.n_ops
    assert r_on.ok and r_on.mis_size == r_on.n_ops == 74
    assert r_on.report is not None and r_on.report.ok
