"""Tracing demo: map two kernels with a live `repro.obs.Tracer`, write
Perfetto-openable Chrome trace JSON under ``artifacts/trace/``, and
print the per-phase wall breakdown (self time, the share of map-dfg's
wall, and the whole durations).

Two workloads, deliberately different phase profiles:

- **C5K5** (paper kernel, 4x4 fabric): certificate stages + the exact
  CSP fast path dominate — the portfolio barely runs.
- **tight 16x16** (`make_tightly_coupled` on a 16x16 PEA, group-move
  kick on): the portfolio harvest rounds dominate, and the rounds'
  ``coverage`` attribute shows the kick breaking the stall.

A third leg demos the rest of the observability surface: a
flight-recorded failure rendered as an explain report
(`MappingResult.explain()`), and a small serve batch's Prometheus
exposition + JSONL access log (`serve.MappingService`).

Open the written ``.trace.json`` files at https://ui.perfetto.dev (or
chrome://tracing) to see the span timelines.

  PYTHONPATH=src python examples/trace_demo.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import (CGRAConfig, cnkm_name, make_cnkm,  # noqa: E402
                        make_request_trace, make_tightly_coupled,
                        map_dfg)
from repro.obs import (FlightRecorder, Tracer,             # noqa: E402
                       write_chrome_trace)

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                   "trace")


def _print_breakdown(name: str, tracer: Tracer) -> None:
    bd = tracer.phase_breakdown()
    total = sum(a["total_s"] for n, a in bd.items() if n == "map-dfg")
    print(f"\n{name}: phase breakdown "
          f"({len(tracer.finished)} spans, map-dfg {total * 1e3:.1f} ms)")
    print(f"  {'phase':<16} {'count':>6} {'self ms':>10} {'share':>7} "
          f"{'total ms':>10}")
    for phase, agg in bd.items():
        share = agg["self_s"] / total if total else 0.0
        print(f"  {phase:<16} {agg['count']:>6} "
              f"{agg['self_s'] * 1e3:>10.2f} {share:>6.1%} "
              f"{agg['total_s'] * 1e3:>10.2f}")
    counters = tracer.registry.snapshot()["counters"]
    if counters:
        print("  counters: " + ", ".join(
            f"{k}={int(v)}" for k, v in sorted(counters.items())))


def main() -> None:
    runs = []

    # Paper kernel on the default 4x4 fabric.
    tr = Tracer()
    r = map_dfg(make_cnkm(5, 5), CGRAConfig(), tracer=tr)
    print(f"{cnkm_name(5, 5)}: {r.summary()}")
    runs.append((cnkm_name(5, 5), "c5k5", tr))

    # Tightly-coupled workload on a 16x16 PEA: the portfolio (with the
    # group-move kick) does the heavy lifting, so the breakdown tilts
    # the other way.
    big = CGRAConfig(rows=16, cols=16)
    tight = make_tightly_coupled(8, 8, 2, link_run=4, seed=0)
    tr2 = Tracer()
    r2 = map_dfg(tight, big, certify=False, mis_restarts=4,
                 mis_iters=2500, min_ii=2, max_ii=2, group_move=True,
                 max_bus_fanout=4, seed=0, tracer=tr2)
    print(f"tight16x16: {r2.summary()}")
    runs.append(("tight16x16", "tight16x16", tr2))

    for name, slug, tracer in runs:
        path = write_chrome_trace(
            tracer, os.path.join(ART, f"{slug}.trace.json"),
            process_name=name)
        print(f"wrote {os.path.relpath(path)} "
              f"(open at https://ui.perfetto.dev)")
        _print_breakdown(name, tracer)

    explain_and_serve_demo()


def explain_and_serve_demo() -> None:
    """Explain report on a flight-recorded infeasibility proof, then a
    small serve batch's Prometheus + access-log exposition."""
    from repro.serve import MappingService, MapRequest

    print("\n--- explain report (proved-infeasible C2K8 BusMap) ---")
    rec = FlightRecorder()
    res = map_dfg(make_cnkm(2, 8), CGRAConfig(), mode="busmap",
                  max_ii=2, record=rec)
    print(res.explain().render())

    print("\n--- serve exposition (8-request Zipf batch) ---")
    svc = MappingService(shard="demo", trace_sample=0.25)
    trace = make_request_trace(8, scale="4x4", seed=3)
    svc.map_batch([MapRequest(dfg=t.dfg, cgra=CGRAConfig(),
                              deadline=t.deadline, req_id=f"r{i}")
                   for i, t in enumerate(trace)])
    print(svc.prometheus(), end="")
    print("access log (last 3 lines):")
    for entry in svc.access_log.tail(3):
        print(f"  {entry}")
    print(f"sampled traces: {len(svc.traces)} "
          f"(head-sampled at rate {svc.trace_sample})")


if __name__ == "__main__":
    main()
