"""LRF-pressure edges (`conflict.lrf_pressure_edges`): two quad ops whose
schedule-fixed LRF charges together overflow a PE's LRF never share a
PE.  The edges are sound (no validator-accepted placement holds one),
the pair floor never exceeds what the validator charges, the edges
prove loop4x4s124's II 2 unbindable at the jitter the pairwise graph
used to leave open, and a schedule with no overflowing pair keeps its
graph byte for byte."""

import dataclasses
import json
import os
import re

import numpy as np
import pytest

from _paper_table_schedules import requests as paper_requests
from repro.core import conflict, map_dfg
from repro.core.bitset import BitsetGraph
from repro.core.certify import certify_ii_infeasible
from repro.core.cgra import CGRAConfig
from repro.core.conflict import (QUAD, build_conflict_graph,
                                 lrf_pair_floor, lrf_pressure_edges)
from repro.core.kernels_polybench import build
from repro.core.schedule import schedule_dfg
from repro.core.validate import validate_mapping
from repro.core.workloads import make_cnkm, make_loop_kernel
from repro.obs.trace import Tracer

CGRA = CGRAConfig()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def polybench_requests():
    with open(os.path.join(ROOT, "bench", "traffic",
                           "polybench_table.json")) as f:
        cycle = json.load(f)["cycle"]
    return [(it["name"], build(it["kernel"], it["unroll"]), it["mode"])
            for it in cycle]


REQUESTS = {name: (d, mode)
            for name, d, mode in paper_requests() + polybench_requests()}


def loop124():
    return make_loop_kernel(2, 4, 3, 2, n_carries=124 % 3, max_distance=2,
                            seed=124)


def without_pass(monkeypatch):
    monkeypatch.setattr(conflict, "lrf_pressure_edges", lambda *a: 0)


def lrf_only(cg, sched, cgra=CGRA) -> tuple[BitsetGraph, int]:
    """The LRF-pressure edges of ``cg``'s schedule alone."""
    g = BitsetGraph(cg.n)
    return g, lrf_pressure_edges(g, cg.vertices, cg.op_vertices, sched,
                                 cgra)


# ------------------------------------------------- (a) accepted mappings
@pytest.mark.parametrize("name", list(REQUESTS))
def test_accepted_placements_hold_no_lrf_edge(name, monkeypatch):
    """Every request of both benchmark mixes, mapped on a graph without
    the pass at two seeds: the placement the validator accepted selects
    no two endpoints of an LRF-pressure edge of its schedule."""
    dfg, mode = REQUESTS[name]
    without_pass(monkeypatch)
    for seed in (0, 1):
        r = map_dfg(dfg, CGRA, mode=mode, seed=seed, engine="numpy")
        assert r.ok and r.report.ok
        cg = build_conflict_graph(r.sched, CGRA, bus_pressure=True)
        g, _ = lrf_only(cg, r.sched)
        sel = np.array(sorted(v.idx for v in r.placement.values()))
        assert len(sel) == len(r.sched.dfg.ops)
        assert not g.rows_u8(sel)[:, sel].any()


def test_binding_schedules_gain_lrf_edges():
    """The check above is not vacuous: the binding schedules of these
    paper_table requests do gain LRF-pressure edges."""
    for name in ("C2K4.bandmap", "C4K4.busmap", "loop4x4s102",
                 "loop4x4s108", "loop4x4s124"):
        dfg, mode = REQUESTS[name]
        r = map_dfg(dfg, CGRA, mode=mode, engine="numpy")
        cg = build_conflict_graph(r.sched, CGRA, bus_pressure=True)
        assert lrf_only(cg, r.sched)[1] > 0, name


# ------------------------------------- (b) the floor against the validator
def _schedules():
    """Schedules with COMPUTE and ROUTE ops, bus and staggered VIOs."""
    return {
        "loop4x4s124.ii2.j3": schedule_dfg(loop124(), CGRA, ii=2, max_ii=2,
                                           jitter=3),
        "C5K5.busmap.ii3": schedule_dfg(make_cnkm(5, 5), CGRA,
                                        mode="busmap", ii=3, max_ii=3),
        "C4K4.ii1": schedule_dfg(make_cnkm(4, 4), CGRA, ii=1, max_ii=1),
        "seidel-2d.u4.ii28": schedule_dfg(build("seidel-2d", 4), CGRA,
                                          ii=28, max_ii=28),
    }


SCHEDULES = _schedules()
_PEAK = re.compile(r"LRF overflow on PE \((\d+), (\d+)\): (\d+) > 0")


def _random_placement(cg, rng, n_pes: int) -> dict:
    """One candidate per op; quad ops drawn onto ``n_pes`` random PEs,
    so that many pairs share one."""
    pes = {tuple(p) for p in rng.permutation(
        [(r, c) for r in range(CGRA.rows) for c in range(CGRA.cols)]
    )[:n_pes].tolist()}
    out = {}
    for op, vis in cg.op_vertices.items():
        cands = [i for i in vis if cg.vertices[i].kind != QUAD
                 or cg.vertices[i].pe in pes]
        out[op] = cg.vertices[cands[rng.integers(len(cands))]]
    return out


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
@pytest.mark.parametrize("name", list(SCHEDULES))
def test_pair_floor_never_exceeds_the_validator(name, seed):
    """Random complete placements, their quad ops crowded onto one to
    four PEs: every PE's LRF peak as the validator counts it is at
    least the pair floor of every two ops on it.  An LRF of 0 makes the
    validator report each loaded PE's peak."""
    sched = SCHEDULES[name]
    cg = build_conflict_graph(sched, CGRA)
    quad, floor = lrf_pair_floor(sched)
    row = {o: k for k, o in enumerate(quad)}
    peak_of = floor.max(axis=2)
    bare = dataclasses.replace(CGRA, lrf=0)
    rng = np.random.default_rng(seed)
    checked = 0
    for n_pes in (1, 2, 3, 4) * 3:
        placement = _random_placement(cg, rng, n_pes)
        report = validate_mapping(sched, bare, placement)
        peak = {(int(r), int(c)): int(p) for r, c, p in
                _PEAK.findall(" ".join(report.violations))}
        by_pe: dict = {}
        for o, v in placement.items():
            if v.kind == QUAD:
                by_pe.setdefault(v.pe, []).append(row[o])
        for pe, ks in by_pe.items():
            for i, a in enumerate(ks):
                for b in ks[i + 1:]:
                    assert peak_of[a, b] <= peak.get(pe, 0)
                    checked += 1
    assert checked > 0


# ------------------------------------------ (c) loop4x4s124's II 2 proof
def test_loop124_ii2_jitter3_certified_with_the_pass(monkeypatch):
    """II 2 jitter 3 of loop4x4s124: ops whose operand latches alone
    fill more than the LRF never share a PE, and the exact search
    proves the schedule unbindable.  Without the pass the pairwise
    graph leaves it open."""
    sched = schedule_dfg(loop124(), CGRA, ii=2, max_ii=2, jitter=3)
    cert, _ = certify_ii_infeasible(
        build_conflict_graph(sched, CGRA, bus_pressure=True), sched, CGRA,
        jitter=3)
    assert cert is not None and cert.stage == "exhausted"
    without_pass(monkeypatch)
    cert, sols = certify_ii_infeasible(
        build_conflict_graph(sched, CGRA, bus_pressure=True), sched, CGRA,
        jitter=3)
    assert cert is None and sols


def test_loop124_maps_without_repair():
    """The map of loop4x4s124 keeps its II and routing PEs, and no
    longer harvests at II 2: no repair, one harvest round at II 3."""
    tr = Tracer()
    r = map_dfg(loop124(), CGRA, engine="numpy", tracer=tr)
    assert r.ok and r.ii == 3 and r.n_routing_pes == 0
    names = [s.name for s in tr.finished]
    assert "repair" not in names and names.count("portfolio") == 1
    assert any(c.ii == 2 and c.jitter == 3 and c.stage == "exhausted"
               for c in r.certificates)


# --------------------------------------------- (d) byte-identical graphs
@pytest.mark.parametrize("name", ["loop4x4s100", "loop4x4s128",
                                  "seidel-2d.u4.busmap", "C3K6.busmap"])
def test_no_overflowing_pair_keeps_the_graph(name, monkeypatch):
    """A schedule with no overflowing pair builds the same adjacency
    with and without the pass, and counts nothing."""
    dfg, mode = REQUESTS[name]
    sched = map_dfg(dfg, CGRA, mode=mode, engine="numpy").sched
    tr = Tracer()
    on = build_conflict_graph(sched, CGRA, bus_pressure=True, tracer=tr)
    assert lrf_only(on, sched)[1] == 0
    assert all("conflict.lrf_edges" not in s.counts for s in tr.finished)
    without_pass(monkeypatch)
    off = build_conflict_graph(sched, CGRA, bus_pressure=True)
    assert np.array_equal(on.bits.rows, off.bits.rows)


def test_pressure_off_keeps_the_seed_graph(monkeypatch):
    """``bus_pressure=False`` runs no capacity pass: on a schedule whose
    pairs do overflow, the graph is the same as without the pass."""
    sched = schedule_dfg(loop124(), CGRA, ii=2, max_ii=2, jitter=3)
    tr = Tracer()
    on = build_conflict_graph(sched, CGRA, bus_pressure=True, tracer=tr)
    assert [s.counts for s in tr.finished] == [{"conflict.lrf_edges": 208}]
    plain = build_conflict_graph(sched, CGRA, bus_pressure=False)
    without_pass(monkeypatch)
    seed = build_conflict_graph(sched, CGRA, bus_pressure=False)
    assert np.array_equal(plain.bits.rows, seed.bits.rows)
    assert not np.array_equal(on.bits.rows, plain.bits.rows)
