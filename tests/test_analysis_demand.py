"""Implied-bandwidth-demand analysis: soundness against the engines.

Three layers of evidence that `repro.analysis.demand` keeps its
contract ("a bound of II >= k means the deterministic schedule family
has no binding below k"):

1. constructed dense-VIO / dense-VOO scenarios where the tuple bound
   fires and `exact_map_dfg` — exhaustive over the same schedule
   family — independently proves UNSAT below the static floor;
2. the scenario the ISSUE names: a dense-VIO component that
   `exact.hall.hall_pressure_edges` alone contributes *zero* edges
   for at the infeasible II (no routing ops, no forced drives), so
   only the tuple demand bound prunes it pre-mapping;
3. a no-false-positive sweep: on every shipped paper kernel and
   workload family the analyzer is a provable no-op (no error
   findings, no bound above MII), and mapped representatives always
   achieve an II >= the static floor.
"""

from __future__ import annotations

import pytest

from _hypothesis_compat import given, settings, st

from repro.analysis import analyze, static_infeasibility
from repro.analysis.demand import (DemandBound, demand_mii,
                                   effective_fanout,
                                   implied_demand_bounds)
from repro.analysis.dfglint import fatal_findings, lint_dfg
from repro.core import map_dfg
from repro.core.cgra import CGRAConfig
from repro.core.conflict import build_conflict_graph
from repro.core.dfg import DFG, OpKind
from repro.core.kernels_cnkm import all_paper_kernels, make_cnkm
from repro.core.schedule import mii, schedule_dfg
from repro.core.workloads import serve_catalog, sweep_specs
from repro.exact import exact_map_dfg
from repro.exact.hall import hall_pressure_edges

CGRA = CGRAConfig()


# ------------------------------------------------------- constructors
def dense_vio(k: int) -> DFG:
    """k VINs chained into one row component: compute x_i reads
    {v_i, v_{i+1}}, so consecutive VINs share a consumer and the
    union-find ties all k into one component -> static floor k."""
    d = DFG()
    vins = [d.add_op(OpKind.VIN, f"v{i}") for i in range(k)]
    for i in range(k - 1):
        x = d.add_op(OpKind.COMPUTE, f"x{i}")
        d.add_edge(vins[i], x)
        d.add_edge(vins[i + 1], x)
        o = d.add_op(OpKind.VOUT, f"o{i}")
        d.add_edge(x, o)
    return d


def dense_voo(k: int) -> DFG:
    """One producer feeding k VOUTs: the column-side dual, floor k."""
    d = DFG()
    v = d.add_op(OpKind.VIN, "v")
    p = d.add_op(OpKind.COMPUTE, "p")
    d.add_edge(v, p)
    for i in range(k):
        q = d.add_op(OpKind.VOUT, f"q{i}")
        d.add_edge(p, q)
    return d


# ------------------------------------------------- the bound itself
def test_dense_vio_bound_fires():
    bounds = implied_demand_bounds(dense_vio(3), CGRA)
    assert len(bounds) == 1
    b = bounds[0]
    assert isinstance(b, DemandBound)
    assert b.scope == "row"
    assert b.min_ii == 3
    assert len(b.tuple_ops) == 3
    assert "II >= 3" in b.summary()
    assert demand_mii(dense_vio(3), CGRA) == 3


def test_dense_voo_bound_fires():
    bounds = implied_demand_bounds(dense_voo(2), CGRA)
    assert [b.scope for b in bounds] == ["col"]
    assert bounds[0].min_ii == 2


def test_high_fanout_vio_exempt():
    """A VIN with rd > m_eff is GRF/multi-port material — the
    single-port row-pinning argument does not apply, so it must never
    enter a component."""
    m_eff = effective_fanout(CGRA)
    d = DFG()
    v = d.add_op(OpKind.VIN, "v")
    outs = []
    for i in range(m_eff + 1):
        x = d.add_op(OpKind.COMPUTE, f"x{i}")
        d.add_edge(v, x)
        outs.append(x)
    o = d.add_op(OpKind.VOUT, "o")
    d.add_edge(outs[0], o)
    assert implied_demand_bounds(d, CGRA) == []
    # ... but a max_bus_fanout override can pull it back in scope.
    assert effective_fanout(CGRA, max_bus_fanout=1) == 1


def test_effective_fanout_matches_scheduler():
    assert effective_fanout(CGRA) == CGRA.pes_per_ibus
    assert effective_fanout(CGRA, max_bus_fanout=2) == 2
    assert effective_fanout(CGRA, max_bus_fanout=99) == CGRA.pes_per_ibus
    assert effective_fanout(CGRA, max_bus_fanout=0) == 1


# ------------------------------------- differential: exact backend
@given(st.integers(min_value=2, max_value=4))
@settings(max_examples=3, deadline=None)
def test_exact_confirms_dense_vio_floor(k):
    """Every flagged (DFG, II < floor) is UNSAT-proved by the
    exhaustive backend over the same schedule family."""
    d = dense_vio(k)
    assert demand_mii(d, CGRA) == k
    r = exact_map_dfg(d, CGRA, max_ii=k - 1)
    assert not r.ok
    assert r.proved_infeasible


def test_exact_confirms_dense_voo_floor():
    d = dense_voo(2)
    r = exact_map_dfg(d, CGRA, max_ii=1)
    assert not r.ok and r.proved_infeasible


def test_exact_confirms_structural_errors():
    """The two absolute error rules (VIN with a predecessor, VOUT with
    a successor) describe ops `conflict._dep_ok` can never bind — the
    exhaustive backend agrees at every II it tries."""
    d = DFG()
    a = d.add_op(OpKind.VIN, "a")
    x = d.add_op(OpKind.COMPUTE, "x")
    b = d.add_op(OpKind.VIN, "b")
    d.add_edge(a, x)
    d.add_edge(x, b)
    assert any(f.rule == "vin-has-pred" for f in lint_dfg(d, CGRA))
    r = exact_map_dfg(d, CGRA, max_ii=3)
    assert not r.ok and r.proved_infeasible

    d2 = DFG()
    a = d2.add_op(OpKind.VIN, "a")
    x = d2.add_op(OpKind.COMPUTE, "x")
    o = d2.add_op(OpKind.VOUT, "o")
    y = d2.add_op(OpKind.COMPUTE, "y")
    d2.add_edge(a, x)
    d2.add_edge(x, o)
    d2.add_edge(o, y)
    assert any(f.rule == "vout-has-succ" for f in lint_dfg(d2, CGRA))
    r2 = exact_map_dfg(d2, CGRA, max_ii=3)
    assert not r2.ok and r2.proved_infeasible


# --------------------------------------- the shape hall.py misses
def test_hall_alone_misses_dense_vio():
    """The dense-VIO scenario has no routing ops and no forced drive
    pairs, so `hall_pressure_edges` adds zero edges to its graph — the
    tuple demand bound is the only pre-mapping analysis that sees the
    floor.  The scheduler gives the three tied VIOs distinct delivery
    slots, so it emits no schedule below that floor (II 2), and at the
    floor the graph hall leaves untouched binds."""
    d = dense_vio(3)
    assert demand_mii(d, CGRA) == 3       # the bound sees it
    with pytest.raises(RuntimeError):
        schedule_dfg(d, CGRA, ii=2, max_ii=2)
    sched = schedule_dfg(d, CGRA, ii=3, max_ii=3)
    cg = build_conflict_graph(sched, CGRA, bus_pressure=True)
    n = hall_pressure_edges(cg.bits, cg.vertices, cg.op_vertices,
                            sched, CGRA)
    assert n == 0
    assert map_dfg(d, CGRA, max_ii=3).ii == 3


# ----------------------------------------- map_dfg static pre-pass
def test_map_dfg_skips_below_static_floor():
    r = map_dfg(dense_vio(3), CGRA, max_ii=2)
    assert not r.ok
    assert r.attempts == 0                # never built a schedule
    assert r.proved_infeasible
    assert [(c.ii, c.jitter, c.stage) for c in r.certificates] == \
        [(1, -1, "static-demand"), (2, -1, "static-demand")]


def test_map_dfg_prepass_identical_on_mappable_kernel():
    """On kernels the analyzer is a no-op for, the pre-pass must not
    change the result in any way."""
    d = make_cnkm(2, 4)
    a = map_dfg(d, CGRA, seed=0)
    b = map_dfg(d, CGRA, seed=0, static_prepass=False)
    assert (a.ok, a.ii, a.n_routing_pes, a.attempts, a.placement) == \
        (b.ok, b.ii, b.n_routing_pes, b.attempts, b.placement)


def test_map_dfg_prepass_partial_skip():
    """With max_ii above the floor the engine still runs, but the
    doomed IIs below the floor are certificate-skipped."""
    r = map_dfg(dense_vio(2), CGRA, max_ii=4)
    skipped = [c for c in r.certificates if c.stage == "static-demand"]
    assert [c.ii for c in skipped] == [1]
    assert all(c.jitter == -1 for c in skipped)


# --------------------------------------------- verdict constructor
def test_static_infeasibility_verdict_shape():
    res = static_infeasibility(dense_vio(3), CGRA, max_ii=2)
    assert res is not None
    assert not res.ok and res.proved_infeasible
    assert res.backend == "static"
    assert res.attempts == 0 and res.certificates   # cache-admissible
    assert res.sched is None and res.placement == {}

    # floor within budget -> no verdict, engine must run.
    assert static_infeasibility(dense_vio(3), CGRA, max_ii=8) is None
    assert static_infeasibility(make_cnkm(2, 4), CGRA) is None


def test_static_infeasibility_on_fatal_lint():
    d = DFG()
    a = d.add_op(OpKind.COMPUTE, "a")
    b = d.add_op(OpKind.COMPUTE, "b")
    v = d.add_op(OpKind.VIN, "v")
    o = d.add_op(OpKind.VOUT, "o")
    d.add_edge(v, a)
    d.add_edge(a, b)
    d.add_edge(b, a)                      # distance-0 cycle
    d.add_edge(b, o)
    assert fatal_findings(lint_dfg(d))
    res = static_infeasibility(d, CGRA, max_ii=8)
    assert res is not None and res.proved_infeasible
    assert "zero-distance-cycle" in res.certificates[0].detail


# --------------------------------------- no-false-positive sweep
def _suite():
    specs = {s.name: s for s in sweep_specs("4x4")}
    specs.update({s.name: s for s in sweep_specs("8x8")})
    specs.update({s.name: s for s in serve_catalog("8x8")})
    return [(name, spec.build()) for name, spec in sorted(specs.items())] \
        + sorted(all_paper_kernels().items())


@pytest.mark.parametrize("name,dfg", _suite(), ids=lambda v: v
                         if isinstance(v, str) else "")
def test_analyzer_noop_on_shipped_workloads(name, dfg):
    """Soundness floor: on every kernel/family the repo ships (all of
    which the portfolio maps elsewhere in the suite), the analyzer
    reports no errors and no demand bound above MII."""
    findings, bounds = analyze(dfg, CGRA)
    assert not fatal_findings(findings), (name, findings)
    assert bounds == [], (name, bounds)
    assert demand_mii(dfg, CGRA) == mii(dfg, CGRA)


@pytest.mark.parametrize("n,m", [(2, 4), (3, 6)])
def test_floor_never_exceeds_achieved_ii(n, m):
    """End-to-end tie: a successful map's II is >= the static floor,
    i.e. the analyzer never flags a combo the engine then achieves."""
    d = make_cnkm(n, m)
    floor = demand_mii(d, CGRA)
    r = map_dfg(d, CGRA, seed=0)
    assert r.ok
    assert r.ii >= floor
