"""`mis.ejection_repair` on integer bitmasks against the frozen numpy
search it replaced (`_ejection_repair_ref`): the same repaired set and
the same number of search nodes on paper kernels, loop kernels, made
shortfalls, every depth and the node budget; and the neighbour masks
memoized on the conflict graph."""

import numpy as np
import pytest

from _ejection_repair_ref import ejection_repair_ref
from _hypothesis_compat import given, settings, st
from repro.core.bitset import BitsetGraph, pack_bool
from repro.core.cgra import CGRAConfig
from repro.core.conflict import build_conflict_graph
from repro.core.kernels_polybench import build
from repro.core.mis import ejection_repair, solve_mis
from repro.core.schedule import schedule_dfg
from repro.core.workloads import make_cnkm, make_loop_kernel
from repro.obs.trace import Tracer

CGRA = CGRAConfig()


def repair(g, sol, op_vertices, op_of, *, depth, seed, masks=None):
    """The program's repair: (repaired set, nodes counted on the
    ``repair`` span)."""
    tr = Tracer()
    with tr.span("repair"):
        out = ejection_repair(g, sol, op_vertices, op_of, depth=depth,
                              seed=seed, masks=masks, tracer=tr)
    (sp,) = tr.finished
    assert sp.counts["repair.nodes"] == tr.counter_value("repair.nodes")
    return out, sp.counts["repair.nodes"]


def assert_same(g, sol, op_vertices, op_of, *, depth, seed, masks=None):
    want, want_nodes = ejection_repair_ref(g, sol, op_vertices, op_of,
                                           depth=depth, seed=seed)
    got, nodes = repair(g, sol, op_vertices, op_of, depth=depth,
                        seed=seed, masks=masks)
    assert got.dtype == bool and got.shape == want.shape
    assert np.array_equal(got, want)
    assert nodes == want_nodes
    return nodes


def shortfall(sol, drop, seed):
    """``sol`` less ``drop`` of its members, chosen by ``seed``."""
    out = sol.copy()
    idx = np.flatnonzero(out)
    rng = np.random.default_rng(seed)
    out[rng.choice(idx, size=min(drop, idx.size), replace=False)] = False
    return out


@given(st.integers(0, 10 ** 6))
@settings(max_examples=10, deadline=None)
def test_same_as_reference_on_cnkm(seed):
    for mode in ("bandmap", "busmap"):
        sched = schedule_dfg(make_cnkm(3, 6), CGRA, mode=mode)
        cg = build_conflict_graph(sched, CGRA)
        sol = solve_mis(cg.bits, max_iters=300, seed=seed)
        for drop in (0, 1, 2, 3, 4):
            cut = shortfall(sol, drop, seed + drop)
            for depth in (1, 2, 3, 4):
                assert_same(cg.bits, cut, cg.op_vertices, cg.op_of,
                            depth=depth, seed=seed * 7 + depth,
                            masks=cg.nbr_masks())


@pytest.mark.parametrize("s", [100, 106, 111, 117, 121, 124, 129])
def test_same_as_reference_on_loop_kernels(s):
    """The benchmark's 4x4 loop kernels at their first schedulable II,
    with shortfalls of 1-4 made by dropping members of a solved set."""
    dfg = make_loop_kernel(2, 4, 3, 2, n_carries=s % 3, max_distance=2,
                           seed=s)
    sched = schedule_dfg(dfg, CGRA)
    cg = build_conflict_graph(sched, CGRA, bus_pressure=True)
    sol = solve_mis(cg.bits, max_iters=2000, seed=s,
                    target=len(cg.op_vertices))
    nodes = 0
    for drop in (1, 2, 3, 4):
        cut = shortfall(sol, drop, s * 10 + drop)
        assert cut.sum() == sol.sum() - drop
        for depth in (1, 2, 3, 4):
            nodes += assert_same(cg.bits, cut, cg.op_vertices, cg.op_of,
                                 depth=depth, seed=s * 100 + depth,
                                 masks=cg.nbr_masks())
    assert nodes > 16


def pigeonhole(k: int):
    """k + 1 ops over k slots: candidate (op, slot) conflicts with every
    other candidate of its op and of its slot.  Ops 0..k-1 sit on their
    own slot; op k has no room, and every eviction chain fails."""
    n_ops = k + 1
    vid = np.arange(n_ops * k).reshape(n_ops, k)
    adj = np.zeros((n_ops * k, n_ops * k), dtype=bool)
    for grp in list(vid) + list(vid.T):
        adj[np.ix_(grp, grp)] = True
    np.fill_diagonal(adj, False)
    op_vertices = {op: [int(v) for v in vid[op]] for op in range(n_ops)}
    op_of = np.repeat(np.arange(n_ops), k)
    sol = np.zeros(n_ops * k, dtype=bool)
    sol[vid[np.arange(k), np.arange(k)]] = True
    return BitsetGraph.from_dense(adj), sol, op_vertices, op_of


@pytest.mark.parametrize("k,depth", [(5, 1), (5, 2), (5, 3), (5, 4),
                                     (15, 4)])
def test_same_as_reference_when_no_chain_closes(k, depth):
    """Every chain fails, so the search walks its whole tree; at 15
    slots and depth 4 that tree passes the 20,000-node budget, and the
    node count there moves if the budget is off by one (20,014 nodes at
    19,999, 20,044 at 20,001)."""
    g, sol, op_vertices, op_of = pigeonhole(k)
    nodes = assert_same(g, sol, op_vertices, op_of, depth=depth, seed=k)
    if k == 15:
        assert nodes == 20029
    else:
        assert nodes < 20000


def test_masks_default_to_the_graph_rows():
    """Without ``masks`` the repair builds them from the graph it is
    given, dense or packed, and answers the same."""
    sched = schedule_dfg(make_cnkm(3, 6), CGRA, mode="busmap")
    cg = build_conflict_graph(sched, CGRA)
    sol = shortfall(solve_mis(cg.bits, max_iters=300, seed=3), 2, 3)
    got = [repair(adj, sol, cg.op_vertices, cg.op_of, depth=3, seed=5,
                  masks=masks)
           for adj, masks in ((cg.adj, None), (cg.bits, None),
                              (cg.bits, cg.nbr_masks()))]
    assert all(np.array_equal(o, got[0][0]) and n == got[0][1]
               for o, n in got)
    assert not cg.bits.any_conflict(pack_bool(got[0][0]))


def test_nbr_masks_are_the_rows_and_built_once(monkeypatch):
    sched = schedule_dfg(make_cnkm(3, 6), CGRA, mode="busmap")
    cg = build_conflict_graph(sched, CGRA)
    builds = []
    real = BitsetGraph.row_masks

    def counted(self):
        builds.append(id(self))
        return real(self)

    monkeypatch.setattr(BitsetGraph, "row_masks", counted)
    masks = cg.nbr_masks()
    assert cg.nbr_masks() is masks and builds == [id(cg.bits)]
    assert len(masks) == cg.n
    nbytes = (cg.n + 7) // 8
    for v, m in enumerate(masks):
        bits = np.unpackbits(np.frombuffer(m.to_bytes(nbytes, "little"),
                                           dtype=np.uint8),
                             bitorder="little", count=cg.n)
        assert np.array_equal(bits, cg.bits.row_u8(v))
        assert np.array_equal(bits.astype(bool), cg.adj[v])


def test_masks_built_once_per_graph_in_a_map(monkeypatch):
    """A map that repairs many times (seidel-2d unrolled four times,
    whose validator rejects no pairwise conflict edge rules out) builds
    each conflict graph's masks at most once."""
    from repro.core import bandmap
    from repro.core.bandmap import map_dfg
    builds, tries = [], []
    real_masks, real_repair = BitsetGraph.row_masks, bandmap.ejection_repair

    def counted(self):
        builds.append(self)               # held, so no id is reused
        return real_masks(self)

    def repair_seen(*args, **kwargs):
        assert kwargs["masks"] is not None
        tries.append(1)
        return real_repair(*args, **kwargs)

    monkeypatch.setattr(BitsetGraph, "row_masks", counted)
    monkeypatch.setattr(bandmap, "ejection_repair", repair_seen)
    res = map_dfg(build("seidel-2d", 4), CGRA)
    assert res.ok
    assert len(tries) > len(builds) > 0
    assert len({id(g) for g in builds}) == len(builds)
