"""Observability layer: span mechanics, the NullTracer bit-identity
contract, export round-trips, and the metrics registry.

The load-bearing test here is the bit-identity sweep: `map_dfg` with a
recording `Tracer` must return exactly the same (ok, II, routing-PE,
attempts) as with ``tracer=None`` on every paper kernel — tracing is
observation only, never a perturbation of the search.  The slow BusMap
stragglers run under ``-m slow``, matching test_golden_results.
"""

import json
import os
import threading
import time
import tracemalloc

import numpy as np
import pytest

from repro.core import PAPER_KERNELS, cnkm_name, make_cnkm, map_dfg
from repro.core.cgra import CGRAConfig
from repro.obs import (NULL_COUNTER, NULL_TRACER, PHASES, MetricsRegistry,
                       NullTracer, SpanRecord, Tracer, from_json, live,
                       to_chrome_trace, to_json)
from repro.obs import trace as trace_mod
from repro.obs.trace import NULL_SPAN


# ---------------------------------------------------------------- spans

def test_span_nesting_parent_and_depth():
    tr = Tracer()
    with tr.span("outer", ii=2) as outer:
        with tr.span("inner", jitter=1) as inner:
            inner.set(nodes=7)
        with tr.span("inner2"):
            pass
    recs = {r.name: r for r in tr.finished}
    assert set(recs) == {"outer", "inner", "inner2"}
    assert recs["outer"].parent == -1 and recs["outer"].depth == 0
    assert recs["inner"].parent == recs["outer"].sid
    assert recs["inner"].depth == 1
    assert recs["inner2"].parent == recs["outer"].sid
    assert recs["inner"].attrs == {"jitter": 1, "nodes": 7}
    assert recs["outer"].attrs == {"ii": 2}
    # Children finish before the parent; times are monotone and nested.
    assert recs["inner"].t1 <= recs["outer"].t1
    assert recs["outer"].t0 <= recs["inner"].t0
    assert all(r.dur_s >= 0 for r in tr.finished)
    assert outer.sid != inner.sid


def test_span_records_error_attr_on_exception():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("nope")
    (rec,) = tr.finished
    assert rec.attrs["error"] == "ValueError"


def test_span_out_of_order_exit_tolerated():
    tr = Tracer()
    outer = tr.span("outer")
    tr.span("inner")  # never explicitly closed
    outer.__exit__(None, None, None)  # closes through the stack
    names = [r.name for r in tr.finished]
    assert names == ["outer"]
    # A fresh span after the unwind starts at the top level again.
    with tr.span("next"):
        pass
    assert tr.finished[-1].parent == -1


def test_spans_from_two_threads_keep_separate_stacks():
    tr = Tracer()

    def work(tag):
        with tr.span("side", side=tag):
            with tr.span("leaf", side=tag):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    leaves = [r for r in tr.finished if r.name == "leaf"]
    sides = {r.attrs["side"]: r for r in tr.finished if r.name == "side"}
    assert len(leaves) == 2 and len(sides) == 2
    for leaf in leaves:
        # Each leaf's parent is its own thread's "side" span.
        assert leaf.parent == sides[leaf.attrs["side"]].sid
        assert leaf.tid == sides[leaf.attrs["side"]].tid


def test_phase_breakdown_aggregates_and_sorts():
    tr = Tracer()
    for _ in range(3):
        with tr.span("a"):
            pass
    with tr.span("b"):
        pass
    bd = tr.phase_breakdown()
    assert bd["a"]["count"] == 3 and bd["b"]["count"] == 1
    totals = [agg["total_s"] for agg in bd.values()]
    assert totals == sorted(totals, reverse=True)


def test_phase_breakdown_self_time_partitions_the_root():
    """``self_s`` is the duration less the direct children's, the same
    self time the benchmark's layer metrics read; summed over names it
    is the root span's wall."""
    tr = Tracer()
    with tr.span("root"):
        with tr.span("mid"):
            with tr.span("leaf"):
                time.sleep(0.002)
            time.sleep(0.002)
        with tr.span("leaf"):
            time.sleep(0.002)
    recs = {r.sid: r for r in tr.finished}
    bd = tr.phase_breakdown()
    for name in ("root", "mid", "leaf"):
        own = sum(r.dur_s - sum(c.dur_s for c in recs.values()
                                if c.parent == r.sid)
                  for r in recs.values() if r.name == name)
        assert bd[name]["self_s"] == pytest.approx(own, abs=1e-12)
        assert 0 <= bd[name]["self_s"] <= bd[name]["total_s"]
    assert sum(a["self_s"] for a in bd.values()) == \
        pytest.approx(bd["root"]["total_s"], abs=1e-9)
    assert bd["leaf"]["self_s"] == bd["leaf"]["total_s"]


# ------------------------------------------------------ counts on spans

def test_counts_land_on_the_innermost_open_span():
    tr = Tracer()
    tr.count("outside")                  # no span open: registry only
    with tr.span("outer"):
        tr.count("repair.tries")
        handle = tr.counter("portfolio.iters")
        handle.inc(3)
        with tr.span("inner"):
            tr.count("repair.tries", 2)
            handle.inc(5)
            tr.count("validate.calls")
        tr.count("repair.tries")
    recs = {r.name: r for r in tr.finished}
    # Self counts: what was counted while the child was open is the
    # child's, and the handle attributes exactly like `count`.
    assert recs["outer"].counts == {"repair.tries": 2,
                                    "portfolio.iters": 3}
    assert recs["inner"].counts == {"repair.tries": 2,
                                    "portfolio.iters": 5,
                                    "validate.calls": 1}
    # The registry totals are unchanged by the span bookkeeping.
    assert tr.counter_value("repair.tries") == 4
    assert tr.counter_value("portfolio.iters") == 8
    assert tr.counter_value("outside") == 1
    assert handle.value == 8
    # A span with nothing counted carries an empty dict.
    with tr.span("quiet"):
        pass
    assert tr.finished[-1].counts == {}


def test_counts_of_two_threads_stay_apart():
    tr = Tracer()
    barrier = threading.Barrier(2)

    def work(tag, n):
        handle = tr.counter("portfolio.iters")
        with tr.span("side", side=tag):
            barrier.wait(timeout=30)     # both spans open at once
            for _ in range(n):
                tr.count("repair.tries")
                handle.inc(2)
            barrier.wait(timeout=30)

    threads = [threading.Thread(target=work, args=(tag, n))
               for tag, n in ((0, 300), (1, 700))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    sides = {r.attrs["side"]: r for r in tr.finished}
    assert sides[0].counts == {"repair.tries": 300, "portfolio.iters": 600}
    assert sides[1].counts == {"repair.tries": 700,
                               "portfolio.iters": 1400}
    assert tr.counter_value("repair.tries") == 1000


def test_span_counts_sum_to_the_registry_totals():
    """Every increment of a traced map is made inside its ``map-dfg``
    span, so the spans' self counts add up to the registry's totals."""
    tr = Tracer()
    r = map_dfg(make_cnkm(5, 5), CGRAConfig(), tracer=tr)
    assert r.ok
    summed: dict = {}
    for rec in tr.finished:
        for k, v in rec.counts.items():
            summed[k] = summed.get(k, 0) + v
    totals = tr.registry.snapshot()["counters"]
    assert {k: v for k, v in summed.items() if v} == \
        {k: v for k, v in totals.items() if v}
    assert summed["certify.csp_nodes"] > 0
    assert summed["validate.calls"] - summed.get("validate.rejects", 0) \
        == 1


def test_null_tracer_counting_allocates_nothing():
    """The untraced hot path: counts, counter handles and spans of the
    `NullTracer` leave no allocation behind and take no lock."""
    nt = live(None)
    handle = nt.counter("portfolio.iters")

    def hot(n):
        for _ in range(n):
            nt.count("repair.tries")
            handle.inc(3)
            with nt.span("repair"):
                nt.count("validate.rejects")

    hot(10)                               # warm any lazy interpreter state
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        hot(5000)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    obs_dir = os.path.dirname(trace_mod.__file__)
    grown = [d for d in after.compare_to(before, "filename")
             if d.traceback[0].filename.startswith(obs_dir)
             and d.size_diff > 0]
    assert grown == []
    assert handle is NULL_COUNTER
    assert not hasattr(nt, "_lock") and not hasattr(nt, "_tls")


def test_counts_round_trip_through_json_and_chrome():
    tr = Tracer()
    with tr.span("repair", shortfall=2):
        tr.count("repair.tries", 6)
        tr.count("repair.fixed")
        tr.count("repair.nodes", 540)
    with tr.span("validate"):
        tr.count("validate.calls")
    payload = json.loads(json.dumps(to_json(tr)))
    spans = from_json(payload)
    assert spans == tr.finished
    assert [s.counts for s in spans] == [
        {"repair.tries": 6, "repair.fixed": 1, "repair.nodes": 540},
        {"validate.calls": 1}]
    # A payload written before spans carried counts still loads.
    for sp in payload["spans"]:
        del sp["counts"]
    assert [s.counts for s in from_json(payload)] == [{}, {}]
    doc = json.loads(json.dumps(to_chrome_trace(tr)))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert xs[0]["args"] == {"shortfall": 2, "counts": {
        "repair.tries": 6, "repair.fixed": 1, "repair.nodes": 540}}
    assert xs[1]["args"] == {"counts": {"validate.calls": 1}}


def test_repair_and_validate_counts_on_the_device_engine(monkeypatch):
    """A traced device-engine map (interpret mode on the CPU) of a loop
    kernel that repairs: every `ejection_repair` call is one
    ``repair.tries`` on its ``repair`` span, and adds its search nodes
    (as the frozen reference search counts them) to ``repair.nodes``
    there; every ``validate`` span is one ``validate.calls``, and
    exactly one validated candidate is kept."""
    from _ejection_repair_ref import ejection_repair_ref
    from repro.core import bandmap
    from repro.core.workloads import make_loop_kernel
    calls, nodes = [], []
    real = bandmap.ejection_repair

    def counted(*args, **kwargs):
        out = real(*args, **kwargs)
        want, n = ejection_repair_ref(*args, depth=kwargs["depth"],
                                      seed=kwargs["seed"])
        assert np.array_equal(out, want)
        calls.append(int(out.sum()))
        nodes.append(n)
        return out

    monkeypatch.setattr(bandmap, "ejection_repair", counted)
    dfg = make_loop_kernel(2, 4, 3, 2, n_carries=128 % 3,
                           max_distance=2, seed=128)
    tr = Tracer()
    res = map_dfg(dfg, CGRAConfig(), engine="device", tracer=tr)
    assert res.ok
    recs = tr.finished
    counts = {n: tr.counter_value(n) for n in (
        "repair.tries", "repair.fixed", "repair.nodes", "validate.calls",
        "validate.rejects")}
    assert calls and counts["repair.tries"] == len(calls)
    assert counts["repair.nodes"] == sum(nodes) >= len(calls)
    assert counts["repair.fixed"] == sum(c >= res.n_ops for c in calls)
    validates = [r for r in recs if r.name == "validate"]
    assert counts["validate.calls"] == len(validates)
    assert counts["validate.calls"] - counts["validate.rejects"] == 1
    # Each count sits on the span of its work, as a self count.
    where = {}
    for r in recs:
        for k, v in r.counts.items():
            where.setdefault(k, set()).add(r.name)
    assert where["repair.tries"] == where["repair.fixed"] == \
        where["repair.nodes"] == {"repair"}
    assert where["validate.calls"] == where["validate.rejects"] == \
        {"validate"}
    assert sum(r.counts.get("repair.tries", 0) for r in recs) == \
        len(calls)
    assert any(r.name == "portfolio-device" for r in recs)
    # No span opens inside the spans whose self time the benchmark reads.
    sids = {r.sid: r.name for r in recs}
    assert not [r.name for r in recs if sids.get(r.parent) in (
        "repair", "certify", "conflict-build", "portfolio-device")]


# --------------------------------------------------- NullTracer contract

def test_null_tracer_is_allocation_free_singletons():
    nt = live(None)
    assert nt is NULL_TRACER
    assert live(nt) is nt
    tr = Tracer()
    assert live(tr) is tr
    assert nt.span("x", ii=1) is NULL_SPAN
    assert nt.span("y") is nt.span("z")
    c = nt.counter("portfolio.iters")
    c.inc()
    c.inc(5)
    assert nt.counter_value("portfolio.iters") == 0
    nt.count("certify.csp_nodes", 41)
    nt.gauge("queue_depth", 3)
    assert nt.phase_breakdown() == {}
    assert NullTracer().finished == ()
    with nt.span("ctx") as sp:
        assert sp.set(anything=1) is sp


SLOW = {(2, 8, "busmap"), (5, 5, "busmap")}
BIT_CASES = [
    pytest.param(n, m, mode, marks=pytest.mark.slow)
    if (n, m, mode) in SLOW else (n, m, mode)
    for n, m in PAPER_KERNELS for mode in ("bandmap", "busmap")
]


@pytest.mark.parametrize("n,m,mode", BIT_CASES)
def test_tracer_bit_identity_on_paper_kernels(n, m, mode):
    """tracer=None and a recording Tracer must produce the identical
    mapping — tracing never touches the RNG stream or search state."""
    kw = dict(mode=mode, seed=0)
    base = map_dfg(make_cnkm(n, m), CGRAConfig(), **kw)
    tr = Tracer()
    traced = map_dfg(make_cnkm(n, m), CGRAConfig(), tracer=tr, **kw)
    label = f"{cnkm_name(n, m)}:{mode}"
    assert (base.ok, base.ii, base.n_routing_pes, base.attempts) == \
        (traced.ok, traced.ii, traced.n_routing_pes,
         traced.attempts), label
    assert base.mis_size == traced.mis_size, label
    # And the traced run actually recorded the pipeline.
    names = {r.name for r in tr.finished}
    assert "map-dfg" in names and "conflict-build" in names, label
    assert names <= set(PHASES), names - set(PHASES)


def test_traced_run_exports_valid_chrome_trace():
    tr = Tracer()
    r = map_dfg(make_cnkm(5, 5), CGRAConfig(), tracer=tr)
    assert r.ok
    doc = to_chrome_trace(tr, process_name="c5k5")
    # Must survive strict JSON serialization (Perfetto requirement).
    blob = json.loads(json.dumps(doc))
    events = blob["traceEvents"]
    x_names = {e["name"] for e in events if e["ph"] == "X"}
    for phase in ("map-dfg", "conflict-build", "certify", "portfolio",
                  "validate"):
        assert phase in x_names, phase
    for e in events:
        assert e["ph"] in ("X", "C", "M")
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
            assert isinstance(e["tid"], int) and e["tid"] < 64
    (cev,) = [e for e in events if e["ph"] == "C"]
    assert cev["args"]["certify.csp_nodes"] > 0
    assert tr.counter_value("certify.csp_nodes") == \
        cev["args"]["certify.csp_nodes"]


# -------------------------------------------------------- export round-trip

def test_to_json_from_json_round_trip():
    tr = Tracer()
    with tr.span("outer", ii=3):
        with tr.span("inner", stage="exhausted", nodes=12):
            pass
    tr.count("certify.csp_nodes", 12)
    payload = json.loads(json.dumps(to_json(tr)))
    spans = from_json(payload)
    assert spans == tr.finished
    assert all(isinstance(s, SpanRecord) for s in spans)
    assert payload["metrics"]["counters"]["certify.csp_nodes"] == 12


def test_chrome_trace_numpy_attrs_coerced():
    tr = Tracer()
    with tr.span("s", n=np.int64(5), cov=np.float32(0.5),
                 shape=(np.int32(2), 3)):
        pass
    doc = json.loads(json.dumps(to_chrome_trace(tr)))
    args = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]["args"]
    assert args == {"n": 5, "cov": 0.5, "shape": [2, 3]}


# ------------------------------------------------------------- registry

def test_histogram_percentiles_match_numpy():
    reg = MetricsRegistry()
    rng = np.random.default_rng(7)
    samples = rng.exponential(scale=0.01, size=500)
    for s in samples:
        reg.observe("latency_s", float(s))
    p50, p95, p99 = reg.percentiles("latency_s")
    assert p50 == pytest.approx(np.percentile(samples, 50))
    assert p95 == pytest.approx(np.percentile(samples, 95))
    assert p99 == pytest.approx(np.percentile(samples, 99))
    snap = reg.snapshot()
    h = snap["histograms"]["latency_s"]
    assert h["count"] == 500
    assert h["p99"] == pytest.approx(p99)
    assert h["mean"] == pytest.approx(samples.mean())
    assert h["max"] == pytest.approx(samples.max())


def test_gauge_tracks_last_min_max_mean():
    reg = MetricsRegistry()
    for v in (3, 1, 4, 1, 5):
        reg.gauge("queue_depth", v)
    g = reg.snapshot()["gauges"]["queue_depth"]
    assert g == dict(last=5, min=1, max=5, count=5, mean=2.8)


def test_snapshot_reset_drains_window_keeps_lifetime():
    reg = MetricsRegistry()
    reg.inc("portfolio.iters", 10)
    reg.observe("latency_s", 0.5)
    reg.gauge("queue_depth", 3)
    snap = reg.snapshot(reset=True)
    assert snap["counters"]["portfolio.iters"] == 10
    # A second drain sees an empty *window*...
    again = reg.snapshot(reset=True)
    assert again == dict(counters={}, gauges={}, histograms={})
    # ...but the cumulative default view keeps the lifetime totals: a
    # scraping consumer can never zero another reader's view (the
    # double-drain hazard).
    life = reg.snapshot()
    assert life["counters"]["portfolio.iters"] == 10
    assert life["histograms"]["latency_s"]["count"] == 1
    assert life["gauges"]["queue_depth"]["last"] == 3
    # Counters keep accumulating across the drain boundary, and the
    # lifetime reads fold both sides.
    reg.inc("portfolio.iters", 2)
    assert reg.counter_value("portfolio.iters") == 12
    assert reg.snapshot(reset=True)["counters"]["portfolio.iters"] == 2
    assert reg.snapshot()["counters"]["portfolio.iters"] == 12


def test_drained_gauge_envelope_and_percentiles_fold():
    reg = MetricsRegistry()
    reg.gauge("queue_depth", 9)
    for v in (0.1, 0.2):
        reg.observe("latency_s", v)
    reg.snapshot(reset=True)
    reg.gauge("queue_depth", 2)
    reg.observe("latency_s", 0.4)
    g = reg.snapshot()["gauges"]["queue_depth"]
    # Live window's last wins; envelope spans both windows.
    assert (g["last"], g["min"], g["max"], g["count"]) == (2, 2, 9, 2)
    p50, _, _ = reg.percentiles("latency_s")
    assert p50 == pytest.approx(0.2)


def test_concurrent_counter_increments_lossless():
    reg = MetricsRegistry()
    tr = Tracer(registry=reg)
    n_threads, per_thread = 8, 2000

    def work():
        handle = tr.counter("portfolio.iters")
        for _ in range(per_thread):
            handle.inc()
            reg.inc("certify.csp_nodes", 2)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.counter_value("portfolio.iters") == n_threads * per_thread
    assert reg.counter_value("certify.csp_nodes") == \
        n_threads * per_thread * 2
