"""`certify._search_complete` on integer bitmasks against the frozen
numpy search it replaced (`_certify_search_ref`): the same verdict, the
same placements in the same order, the same number of search nodes and
the same orbit skips.

The graphs are every (II, jitter) conflict graph `map_dfg` hands the
search for a sample of the benchmark's ``paper_table`` requests and
four PolyBench kernels, plus fixed graphs driven through a budget that
cuts the search, every placement count, a callback that rejects
placements, a cancel token and a graph with no unpacked row cache."""

import types

import pytest

from _certify_search_ref import search_complete_ref
from _paper_table_schedules import requests
from repro.core import CancelToken, certify
from repro.core.bandmap import map_dfg
from repro.core.certify import _search_complete
from repro.core.cgra import CGRAConfig
from repro.core.conflict import build_conflict_graph
from repro.core.kernels_cnkm import make_cnkm
from repro.core.kernels_polybench import build
from repro.core.mis import ROW_CACHE_LIMIT
from repro.core.schedule import schedule_dfg
from repro.obs.trace import Tracer

CGRA = CGRAConfig()
PAPER = {name: (dfg, mode) for name, dfg, mode in requests()}


def search(fn, cg, budget, **kw):
    """(verdict, placements as bytes, nodes, orbit skips) of one search;
    ``on_solution`` sees (and the result records) every membership it
    was offered."""
    tr = Tracer()
    offered = []
    if "on_solution" in kw:
        accept = kw["on_solution"]

        def on_solution(memb):
            offered.append(memb.tobytes())
            return accept(len(offered))

        kw = {**kw, "on_solution": on_solution}
    verdict, placements, nodes = fn(cg, budget, tracer=tr, **kw)
    assert all(p.dtype == bool and p.shape == (cg.n,) for p in placements)
    return (verdict, [p.tobytes() for p in placements], nodes,
            tr.counter_value("certify.orbit_skips"), offered)


def assert_same(cg, budget, **kw):
    want = search(search_complete_ref, cg, budget, **kw)
    got = search(_search_complete, cg, budget, **kw)
    assert got == want
    return got


def map_calls(dfg, mode, monkeypatch):
    """Every search call of one `map_dfg`: (graph, budget, keywords,
    result with its orbit skips)."""
    calls = []
    real = certify._search_complete

    def recorded(cg, node_budget, **kw):
        tr = Tracer()
        out = real(cg, node_budget, **{**kw, "tracer": tr})
        calls.append((cg, node_budget, kw,
                      out + (tr.counter_value("certify.orbit_skips"),)))
        return out

    monkeypatch.setattr(certify, "_search_complete", recorded)
    assert map_dfg(dfg, CGRA, mode=mode).ok
    return calls


def assert_calls_match_reference(calls):
    for cg, budget, kw, (verdict, placements, nodes, skips) in calls:
        tr = Tracer()
        want = search_complete_ref(cg, budget, **{**kw, "tracer": tr})
        assert verdict is want[0]
        assert [p.tobytes() for p in placements] == \
            [p.tobytes() for p in want[1]]
        assert nodes == want[2]
        assert skips == tr.counter_value("certify.orbit_skips")


@pytest.mark.parametrize("name", ["C2K8.busmap", "C5K5.busmap",
                                  "loop4x4s107", "loop4x4s111",
                                  "loop4x4s124"])
def test_same_as_reference_on_paper_table(name, monkeypatch):
    calls = map_calls(*PAPER[name], monkeypatch)
    assert calls
    assert_calls_match_reference(calls)
    if name.endswith("busmap"):
        # Certified II levels: exhaustions through the symmetry pass.
        assert any(out[0] is False and out[2] > 4096 and out[3] > 0
                   for *_, out in calls)


@pytest.mark.parametrize("kernel", ["jacobi-2d", "seidel-2d", "gemm",
                                    "bicg"])
@pytest.mark.parametrize("unroll", [2, 4])
def test_same_as_reference_on_polybench(kernel, unroll, monkeypatch):
    calls = map_calls(build(kernel, unroll), "busmap", monkeypatch)
    assert calls
    assert_calls_match_reference(calls)


def graph(n, m, mode, ii, jitter):
    sched = schedule_dfg(make_cnkm(n, m), CGRA, mode=mode, ii=ii,
                         max_ii=ii, jitter=jitter)
    return build_conflict_graph(sched, CGRA, bus_pressure=True)


_GRAPHS = {}


def cached(*key):
    if key not in _GRAPHS:
        _GRAPHS[key] = graph(*key)
    return _GRAPHS[key]


# C2K8 BusMap at II 2: no placement; the plain pass stops at 4,096
# nodes and the symmetry pass exhausts the space at 4,206 in all.
EXHAUSTED = (2, 8, "busmap", 2, 0)
# C2K8 BusMap at II 3, jitter 2: placements after a few hundred nodes.
FEASIBLE = (2, 8, "busmap", 3, 2)


@pytest.mark.parametrize("budget", [0, 1, 63, 64, 65, 4095, 4096, 4097,
                                    4150, 200_000])
def test_same_as_reference_under_a_budget(budget):
    cg = cached(*EXHAUSTED)
    verdict, _, nodes, *_ = assert_same(
        cg, budget, row_cache=cg.row_cache(ROW_CACHE_LIMIT), cgra=CGRA)
    if budget < 200_000:
        assert verdict is None and nodes == budget + 1
    else:
        assert verdict is False and nodes == 4206


def test_same_as_reference_when_the_budget_cuts_the_second_pass():
    """C2K8 BusMap at II 3 exhausts in 35,871 nodes; 20,000 stop it in
    the symmetry pass with no answer."""
    cg = cached(2, 8, "busmap", 3, 0)
    verdict, *_ = assert_same(cg, 20_000, cgra=CGRA,
                              row_cache=cg.row_cache(ROW_CACHE_LIMIT))
    assert verdict is None


@pytest.mark.parametrize("n_solutions", [1, 2, 3, 4])
@pytest.mark.parametrize("key", [FEASIBLE, (5, 5, "busmap", 3, 0),
                                 (3, 6, "bandmap", 2, 0)])
def test_same_as_reference_for_each_placement_count(key, n_solutions):
    cg = cached(*key)
    verdict, placements, *_ = assert_same(
        cg, 200_000, row_cache=cg.row_cache(ROW_CACHE_LIMIT), cgra=CGRA,
        n_solutions=n_solutions)
    assert verdict is True and len(placements) == n_solutions


@pytest.mark.parametrize("reject", [0, 1, 2, 3, 10 ** 9])
def test_same_as_reference_when_the_callback_rejects(reject):
    """The callback turns down the first ``reject`` placements; turning
    down every one runs the budget out through both passes."""
    cg = cached(*FEASIBLE)
    verdict, placements, nodes, _, offered = assert_same(
        cg, 12_000, row_cache=cg.row_cache(ROW_CACHE_LIMIT), cgra=CGRA,
        on_solution=lambda i: i > reject)
    if reject < 10 ** 9:
        assert verdict is True and len(offered) == reject + 1
        assert placements == offered[-1:]
    else:
        assert verdict is None and nodes == 12_000 + 1
        assert len(offered) > 4


class PollCount(CancelToken):
    """Cancelled from its ``after``-th poll on."""

    def __init__(self, after: int):
        super().__init__()
        self.after, self.polls = after, 0

    def is_set(self) -> bool:
        self.polls += 1
        return self.polls >= self.after


@pytest.mark.parametrize("after", [1, 3, 64, 65])
def test_same_as_reference_when_cancelled(after):
    """Polls come every 64 nodes.  A cancelled plain pass reads as
    unknown, so the symmetry pass starts and stops at its first poll;
    the 65th poll is the first of the symmetry pass."""
    cg = cached(*EXHAUSTED)
    kw = dict(row_cache=cg.row_cache(ROW_CACHE_LIMIT), cgra=CGRA)
    outs, polls = [], []
    for fn in (search_complete_ref, _search_complete):
        tok = PollCount(after)
        outs.append(search(fn, cg, 200_000, cancel=tok, **kw))
        polls.append(tok.polls)
    assert outs[0] == outs[1] and polls[0] == polls[1]
    assert outs[0][0] is None
    assert outs[0][2] == (4097 + 64 if after > 64 else 64 * after + 64)


@pytest.mark.parametrize("key", [EXHAUSTED, FEASIBLE])
def test_same_as_reference_without_a_row_cache(key):
    """``row_cache_limit=0`` and no cache: the plain pass alone."""
    cg = cached(*key)
    verdict, _, nodes, skips, _ = assert_same(cg, 200_000, cgra=CGRA,
                                              row_cache_limit=0)
    assert skips == 0
    if key == EXHAUSTED:
        assert verdict is None and nodes == 4097


def test_same_as_reference_on_a_duck_typed_graph():
    """A graph with only ``n``, ``bits`` and ``op_vertices`` builds its
    masks from the packed rows."""
    cg = cached(*FEASIBLE)
    duck = types.SimpleNamespace(n=cg.n, bits=cg.bits,
                                 op_vertices=cg.op_vertices)
    got = assert_same(duck, 200_000, n_solutions=4)
    assert got[:3] == search(_search_complete, cg, 200_000,
                             n_solutions=4)[:3]
