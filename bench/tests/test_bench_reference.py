"""The plain reference accepts the program's validated bindings, rejects
every planted fault, and agrees with the program's own validator on the
candidate bindings that validator rejects."""

import dataclasses
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]

from benchkit import dfggen, faults, program, reference  # noqa: E402

FAB = reference.Fabric()
CGRA = program.program_fabric(FAB)
PAPER = [(1, 2), (2, 4), (2, 6), (3, 6), (4, 4), (5, 5)]


def mapped(g, mode="bandmap", **opts):
    res = program.map_request(g, CGRA, {"mode": mode, **opts})
    return res, program.verdict(res)


@pytest.mark.parametrize("n,m", PAPER)
@pytest.mark.parametrize("mode", ["bandmap", "busmap"])
def test_program_bindings_pass(n, m, mode):
    g = dfggen.make_cnkm(n, m)
    res, v = mapped(g, mode)
    assert res.ok
    assert reference.check_verdict(g, FAB, 32, v) == []


def test_loop_bindings_pass_and_mii_agrees():
    from repro.core.schedule import mii
    for s in (100, 107, 111, 113):
        g = dfggen.build("loop", dict(n_chains=2, chain_len=4, n_inputs=3,
                                      n_outputs=2, n_carries=s % 3,
                                      max_distance=2, seed=s))
        res, v = mapped(g)
        assert reference.check_verdict(g, FAB, 32, v) == []
        assert reference.ref_mii(g, FAB) == mii(program.program_dfg(g),
                                                CGRA)


def test_mii_of_16x16_kernels():
    from repro.core.schedule import mii
    fab = reference.Fabric(rows=16, cols=16)
    for s in range(4):
        g = dfggen.build("loop", dict(n_chains=8, chain_len=5, n_inputs=5,
                                      n_outputs=4, n_carries=2,
                                      max_distance=2, cross_links=2,
                                      seed=s))
        assert reference.ref_mii(g, fab) == mii(
            program.program_dfg(g), program.program_fabric(fab))


@pytest.fixture(scope="module")
def busmap_c3k6():
    g = dfggen.make_cnkm(3, 6)
    res, v = mapped(g, "busmap")
    assert v.binding is not None and v.routing_pes > 0
    return g, v


def _b(v, **changes):
    return dataclasses.replace(v, binding=dataclasses.replace(
        v.binding, **changes))


def _first_quad(v):
    return min(op for op, p in v.binding.place.items()
               if p["kind"] == "quad" and p["drive"] is None)


FAULTS = {
    "reported II": lambda v: dataclasses.replace(v, ii=v.ii + 1),
    "reported MII": lambda v: dataclasses.replace(v, mii=v.mii + 1),
    "routing PEs": lambda v: dataclasses.replace(
        v, routing_pes=v.routing_pes - 1),
    "edge dropped": lambda v: _b(v, edges=v.binding.edges[1:]),
    "edge added": lambda v: _b(v, edges=v.binding.edges + [
        v.binding.edges[0]]),
    "edge to nowhere": lambda v: _b(v, edges=v.binding.edges + [
        (v.binding.edges[0][0], 10 ** 6, 0)]),
    "op unplaced": lambda v: _b(v, place={
        k: p for k, p in v.binding.place.items() if k != _first_quad(v)}),
    "late input": lambda v: _b(v, time={
        k: t + (1 if v.binding.ops[k].kind == "vin" else 0)
        for k, t in v.binding.time.items()}),
    "bus assignment lost": lambda v: _b(v, buses={}),
    "wrong slot": lambda v: _b(v, place={
        k: dict(p, m=(p["m"] + 1) % v.binding.ii) if k == _first_quad(v)
        else p for k, p in v.binding.place.items()}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_faults_fail(busmap_c3k6, fault):
    g, v = busmap_c3k6
    assert reference.check_verdict(g, FAB, 32, FAULTS[fault](v))


def test_pe_clash_and_registers(busmap_c3k6):
    g, v = busmap_c3k6
    quads = sorted(op for op, p in v.binding.place.items()
                   if p["kind"] == "quad")
    a, b = quads[0], quads[1]
    # Move b onto a's PE in a's slot: b gets a's time too, keeping the
    # placement consistent with the schedule except for the clash.
    place = dict(v.binding.place)
    place[b] = dict(place[b], pe=place[a]["pe"])
    same_slot = place[a]["m"] == place[b]["m"]
    bad = reference.check_verdict(g, FAB, 32, _b(v, place=place))
    assert bad
    if same_slot:
        assert any("used by ops" in x for x in bad)
    tight = reference.Fabric(lrf=0)
    assert any("LRF" in x for x in
               reference.check_verdict(g, tight, 32, v))


def test_infeasible_verdict_needs_full_proof():
    g = dfggen.make_cnkm(2, 4)
    mii = reference.ref_mii(g, FAB)
    full = reference.Verdict(ok=False, ii=3, mii=mii, routing_pes=0,
                             proved_infeasible=True,
                             certified_iis=tuple(range(mii, 4)))
    assert reference.check_verdict(g, FAB, 3, full) == []
    part = dataclasses.replace(full, certified_iis=(mii,))
    assert reference.check_verdict(g, FAB, 3, part)
    gave_up = dataclasses.replace(full, proved_infeasible=False)
    assert reference.check_verdict(g, FAB, 3, gave_up)


def test_agrees_with_program_validator_on_rejected_candidates():
    """With the program's rejection ignored, the candidates it would
    have rejected reach the reference; it must reject them too, and
    accept the ones the program accepts."""
    from repro.core.validate import validate_mapping
    seen_rejects = 0
    with faults.validator_off():
        for g, mode in [(dfggen.make_cnkm(5, 5), "bandmap"),
                        (dfggen.make_cnkm(2, 8), "busmap")] + [
                (dfggen.build("loop", dict(
                    n_chains=2, chain_len=4, n_inputs=3, n_outputs=2,
                    n_carries=s % 3, max_distance=2, seed=s)), "bandmap")
                for s in (100, 113, 122, 128)]:
            res, v = mapped(g, mode)
            prog_ok = validate_mapping(res.sched, CGRA, res.placement).ok
            ref_ok = not reference.check_verdict(g, FAB, 32, v)
            assert prog_ok == ref_ok, (mode, res.summary())
            seen_rejects += not prog_ok
    assert seen_rejects >= 3
