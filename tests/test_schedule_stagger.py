"""Operands delivered in distinct slots: ops that read two or more VIOs
bind, and every schedule with at most one VIO operand per op stays as it
was.

A bus VIO reaches only its port's row, so the VIO operands of one op
share a row port and need distinct modulo slots (`core/schedule.py`).
"""

from __future__ import annotations

import json

import pytest

from _paper_table_schedules import PINNED, digests, requests
from repro.analysis.demand import demand_mii
from repro.core import map_dfg
from repro.core.cgra import CGRAConfig
from repro.core.dfg import DFG, OpKind
from repro.core.kernels_polybench import KERNELS, build
from repro.core.schedule import schedule_dfg
from repro.core.validate import validate_mapping
from repro.exact import exact_map_dfg
from repro.obs.trace import Tracer
from repro.serve import MappingService, MapRequest

CGRA = CGRAConfig()


def vector_add() -> DFG:
    """``c[i] = a[i] + b[i]``: one op reading two VIOs."""
    d = DFG()
    a = d.add_op(OpKind.VIN, "a[i]")
    b = d.add_op(OpKind.VIN, "b[i]")
    s = d.add_op(OpKind.COMPUTE, "add")
    c = d.add_op(OpKind.VOUT, "c[i]")
    d.add_edge(a, s)
    d.add_edge(b, s)
    d.add_edge(s, c)
    return d


# ----------------------------------------------- the shared scheduler
_PAPER = {name: (d, mode) for name, d, mode in requests()}


@pytest.mark.parametrize("name", sorted(_PAPER))
def test_paper_table_schedules_unchanged(name):
    """Every schedule of the benchmark's paper_table request, at every
    (II, jitter) its warm-up walks, equals the copy pinned before
    operands were staggered, and staggers nothing."""
    d, mode = _PAPER[name]
    with open(PINNED) as f:
        pinned = json.load(f)[name]
    counts = []
    got = digests(d, mode, CGRA,
                  each=lambda s: counts.append(s.stagger_counts()))
    assert got == pinned
    assert counts and all(staggered == hold == 0
                          for _, staggered, hold in counts)
    assert sum(operands for operands, _, _ in counts) > 0


# ------------------------------------------------- two VIO operands
@pytest.mark.parametrize("engine", ["numpy", "device"])
@pytest.mark.parametrize("mode", ["bandmap", "busmap"])
def test_vector_add_binds(mode, engine):
    """Both operands on one row port, one slot apart: II 2, the demand
    floor, with the one II below it certified statically."""
    res = map_dfg(vector_add(), CGRA, mode=mode, engine=engine)
    assert res.ok and not res.proved_infeasible
    assert res.ii == 2 == demand_mii(vector_add(), CGRA)
    assert validate_mapping(res.sched, CGRA, res.placement).ok
    t = res.sched.time
    assert {t[0] % 2, t[1] % 2} == {0, 1} and t[2] == max(t[0], t[1]) + 1
    assert res.placement[0].port == res.placement[1].port
    assert [c.stage for c in res.certificates] == ["static-demand"]


def test_exact_backend_binds_vector_add():
    """The exhaustive prover over the same schedule family finds the
    II the portfolio found, and proves the II below it."""
    res = exact_map_dfg(vector_add(), CGRA, max_ii=4)
    assert res.ok and res.ii == 2 and res.optimal
    assert not res.proved_infeasible


@pytest.mark.parametrize("kernel", KERNELS)
def test_exact_never_beaten_by_the_portfolio(kernel):
    """Certificates stay sound for the staggered family: the exact
    backend binds every kernel at U 1, at an II no larger than the
    portfolio's."""
    d = build(kernel, 1)
    port = map_dfg(d, CGRA)
    ex = exact_map_dfg(d, CGRA, max_ii=port.ii)
    assert ex.ok and ex.ii <= port.ii and not ex.proved_infeasible


def test_map_batch_binds_and_caches_no_negative():
    svc = MappingService(max_workers=1)
    outs = svc.map_batch([MapRequest(dfg=vector_add(), cgra=CGRA,
                                     req_id=f"r{k}", deadline=k)
                          for k in range(2)])
    assert all(o.ok and o.result.ii == 2 for o in outs)
    assert not any(o.source.startswith("negative") for o in outs)
    again = svc.map(vector_add(), CGRA)
    assert again.ok and again.hit and not again.source.startswith("neg")
    assert svc.cache._mem and not any(
        e.negative for e in svc.cache._mem.values())


# ----------------------------------------------- the stagger itself
def test_tied_operands_take_distinct_slots():
    """jacobi-1d U 2: A[i-1], A[i] and A[i+1] are tied to one row port
    through the adds that read two of them, so they need three slots;
    A[i+2] shares no op with another VIO and is scheduled as before."""
    d = build("jacobi-1d", 2)
    assert demand_mii(d, CGRA) == 3
    with pytest.raises(RuntimeError):
        schedule_dfg(d, CGRA, ii=2, max_ii=2)
    s = schedule_dfg(d, CGRA, ii=3, max_ii=3)
    names = {d.ops[v].name: v for v in d.v_i}
    tied = [names[n] for n in ("A[i-1]", "A[i]", "A[i+1]")]
    assert len({s.time[v] % 3 for v in tied}) == 3
    for e in s.dfg.edges:
        if e.src in s.dfg.v_i:
            assert s.time[e.src] < s.time[e.dst]


def test_stagger_counters_on_the_schedule_span():
    """The counters land on the innermost span, `schedule` in
    `map_dfg`, and match the emitted schedule."""
    tr = Tracer()
    res = map_dfg(vector_add(), CGRA, tracer=tr)
    scheds = [r for r in tr.finished if r.name == "schedule"]
    assert scheds
    totals = {k: sum(r.counts.get(k, 0) for r in tr.finished)
              for k in ("schedule.vio_operands", "schedule.staggered",
                        "schedule.hold_cycles")}
    on_schedule = {k: sum(r.counts.get(k, 0) for r in scheds)
                   for k in totals}
    assert totals == on_schedule
    operands, staggered, hold = res.sched.stagger_counts()
    assert (operands, staggered) == (2, 1) and hold >= 1
    assert totals["schedule.vio_operands"] >= operands


def test_stagger_counts_zero_without_shared_consumers():
    from repro.core.kernels_cnkm import make_cnkm
    s = schedule_dfg(make_cnkm(2, 4), CGRA)
    operands, staggered, hold = s.stagger_counts()
    assert operands == 8 and staggered == hold == 0


def test_validator_counts_the_hold_of_an_early_operand():
    """The operand delivered a slot early is latched in the consumer's
    LRF from delivery to use.  At II 2 (a at 0, b at 1, the add at 2)
    slot 0 holds the op's constant, a twice (cycles 0 and 2) and b once
    (cycle 2): 4 registers, so a 3-entry LRF overflows."""
    res = map_dfg(vector_add(), CGRA)
    assert [res.sched.time[i] for i in range(3)] == [0, 1, 2]
    rep = validate_mapping(res.sched, CGRA, res.placement)
    assert rep.ok and rep.lrf_peak == 4
    tight = CGRAConfig(lrf=3)
    rep2 = validate_mapping(res.sched, tight, res.placement)
    assert not rep2.ok and any("LRF" in v for v in rep2.violations)
