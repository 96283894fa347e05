"""Observability layer: spans, metrics, flight events, exposition.

Six modules:

- `trace` — `Tracer` (nestable spans on the monotonic clock, structured
  attributes, counters) and `NullTracer` / `live()` — the ``tracer=None``
  contract that keeps untraced engine runs bit-identical and
  allocation-free.
- `registry` — `MetricsRegistry`: counters, gauges and histograms
  (p50/p95/p99) behind one lock; the backing store for
  `serve.MappingService.metrics()`.  Drained windows
  (``snapshot(reset=True)``) fold into a cumulative store, so one
  consumer's interval scrape never zeroes another's lifetime view.
- `export` — plain-JSON dump and Chrome trace-event serialization; a
  traced `map_dfg` run opens directly in Perfetto / chrome://tracing.
- `flight` — `FlightRecorder`: a bounded lock-guarded ring of
  structured events, cheap enough to stay on in production; failed
  results carry its `dump()` (``MappingResult.flight``).  The
  ``record=None`` contract mirrors ``tracer=None``.
- `explain` — `explain_result` / `ExplainReport`: narrate a
  `MappingResult` (II escalation causes, routing-PE accounting,
  coverage curve, race outcome); also ``MappingResult.explain()`` and
  the ``python -m repro.obs.explain`` CLI.
- `expo` — serve-tier exposition: Prometheus text rendering of
  registry snapshots (with a shard/worker label dimension), the JSONL
  `AccessLog`, and digest-keyed deterministic `head_sample`.

Span taxonomy (STABLE PUBLIC VOCABULARY)
----------------------------------------

These phase names are an interface: `benchmarks/bench_mis.py` records
per-row phase breakdowns keyed on them and `check_regression.py` gates
counters derived from traced runs, so renaming one is a breaking change
to the bench baseline.  Each span is also a `jax.profiler`
annotation of the same name, so a profiled run shows it on the host
plane beside the device operations.  The engine emits:

===============  =====================================================
span name        emitted by / attributes
===============  =====================================================
``map-dfg``      `core.bandmap.map_dfg` root span — ``mode``, ``n_ops``
``static-prepass``  demand-bound II floor pass — ``floor``, ``skipped``
``schedule``     per-(II, jitter) modulo schedule, opened by the walk
                 both backends share (`bandmap._schedules`, so exact
                 traces carry it too) — ``ii``, ``jitter``
``conflict-build``  `conflict.build_conflict_graph` — ``n_vertices``,
                 ``n_edges``; ``interpret`` (Pallas mode) when the
                 packed Pallas kernel built the graph
``certify``      `certify.certify_ii_infeasible` — ``ii``, ``jitter``,
                 ``stage``, ``nodes``, ``orbit_skips``
``portfolio-init``  constructive warm-starts + `PortfolioSBTS` build
                 (on big graphs this is the dominant pre-search cost) —
                 ``ii``, ``jitter``, ``seeds``
``portfolio``    one `PortfolioSBTS` harvest round — ``ii``, ``round``,
                 ``coverage``, ``best``
``portfolio-device``  one `mis_device.DeviceSBTS` harvest round (the
                 accelerator-resident engine, ``engine="device"``) —
                 same attrs as ``portfolio``, plus ``interpret`` (True
                 when the Pallas kernel ran in interpret mode)
``repair``       ejection-chain repair of a near-complete solution
                 (includes the lazy row-cache unpack) — ``shortfall``
``validate``     `validate_mapping` replay of a candidate solution
``exact-csp``    `exact.backend` per-(II, jitter) complete search —
                 ``ii``, ``jitter``, ``verdict``, ``nodes``
``race``         `exact.race` arbitration — ``winner``,
                 ``cancel_latency_s``, ``loser_iters_after_cancel``,
                 ``side_errors`` (only when a side raised)
``race-side``    one side of the race — ``side``, ``wall_s``
``comap-region``  `comap.co_map` per-region mapping — ``region``,
                 ``round``, ``ii``
``arbitrate``    cross-region bus arbitration — ``retries``
``merge-replay``  merged-binding validation in `co_map`
===============  =====================================================

Counters (deterministic; ``check_regression.py`` gates
``certify.csp_nodes`` and ``portfolio.iters``):
``portfolio.iters``, ``portfolio.kicks``, ``certify.csp_nodes``,
``certify.orbit_skips``, ``exact.validations``,
``comap.arbitration_retries``, three counted by `schedule_dfg` for
each schedule it emits under either backend (portfolio or exact), one
counted by `build_conflict_graph` where it is non-zero, and five
counted by the portfolio backend's per-schedule search:

=========================  ================================================
counter                    one per / counted inside
=========================  ================================================
``schedule.vio_operands``  VIO->compute edge of the schedule /
                           ``schedule``
``schedule.staggered``     such edge into an op with two or more VIO
                           operands, delivered before the op's cycle - 1
                           / ``schedule``
``schedule.hold_cycles``   cycle such an operand waits in the op's
                           LRF / ``schedule``
``conflict.lrf_edges``     vertex pair `conflict.lrf_pressure_edges`
                           adds / ``conflict-build``
``repair.tries``           `mis.ejection_repair` call / ``repair``
``repair.fixed``           such call whose result covers every op /
                           ``repair``
``repair.nodes``           search node of such a call, counted once per
                           call by `ejection_repair` / ``repair``
``validate.calls``         `validate_mapping` of a complete candidate, both
                           sources (``csp``, ``portfolio``) / ``validate``
``validate.rejects``       candidate the validator rejected / ``validate``
=========================  ================================================

Counts sit on spans: an increment made through a live `Tracer` also
lands in the ``counts`` of the innermost open span of the calling
thread (`SpanRecord.counts`, self counts like self times), so a
request's spans sum to its totals and a reader of spans needs no
registry.  The registry keeps the same totals.
Gauges: serve's ``queue_depth``.

Flight-event taxonomy (STABLE PUBLIC VOCABULARY)
------------------------------------------------

The flight recorder's event kinds are pinned like ``PHASES`` — the
explain reports and the serve postmortem tooling key on them, so
renaming one is a breaking change to every stored ``flight`` dump:

===============  =====================================================
event kind       emitted by / attributes
===============  =====================================================
``phase-begin``  `map_dfg` major-phase entry — ``phase`` (``map-dfg``,
                 ``static-prepass``), plus the phase's identity attrs
``phase-end``    matching exit — ``phase``, outcome attrs (``ok``,
                 ``ii``, ``floor``, ...)
``attempt``      one (II, jitter) combination entered — ``ii``,
                 ``jitter``
``static-skip``  II below the static demand floor — ``ii``, ``floor``
``certificate``  (II, jitter) proven unbindable — ``ii``, ``jitter``,
                 ``stage``, ``nodes``
``harvest-round``  one portfolio harvest round — ``ii``, ``jitter``,
                 ``round``, ``coverage``, ``best``
``validate-reject``  validator rejected a complete candidate — ``ii``,
                 ``source`` (``csp`` | ``portfolio``)
``cancelled``    cooperative cancel observed — ``ii``
``race-cancel``  `exact.race` cancel request issued — ``winner``
``race-winner``  race arbitration settled — ``winner``,
                 ``cancel_latency_s``, ``side_errors`` (side -> error
                 of a side that raised; empty when none did)
``comap-round``  one co-mapping round finished — ``ii``, ``round``,
                 ``ok_regions``
``comap-arbitrate``  arbitration verdict — ``ii``, ``round``, ``ok``
``serve-admit``  request dispatched to a mapping worker — ``digest``,
                 ``tenant``
``serve-reject``  request resolved without mapping — ``digest``,
                 ``reason`` (``static`` | ``negative-cache``)
``serve-crash``  mapping worker raised — ``digest``, ``error``
===============  =====================================================

Tracer-threading rule (for future engine code)
----------------------------------------------

Every engine entry point takes ``tracer=None`` (keyword-only), converts
it exactly once via ``live(tracer)``, and passes the live handle down.
Code may check ``tracer is None`` / ``is not None`` but must NEVER
branch on trace *content* (span timings, counter values) — tracing is
observation only, and the ``tracer-default-none`` rule in
`repro.analysis.astlint` enforces both halves on the engine modules.
The flight recorder carries the identical contract on its ``record``
parameter (``recording(record)``, ``record is None`` checks only),
enforced by the twin ``recorder-default-none`` rule.
"""

from .registry import NULL_COUNTER, Counter, MetricsRegistry, NullCounter
from .trace import NULL_TRACER, NullTracer, SpanRecord, Tracer, live
from .export import (from_json, to_chrome_trace, to_json,
                     write_chrome_trace, write_json)
from .flight import (NULL_RECORDER, FlightEvent, FlightRecorder,
                     NullFlightRecorder, recording)
from .explain import ExplainReport, explain_result
from .expo import (ACCESS_LOG_FIELDS, AccessLog, head_sample,
                   parse_prometheus, render_prometheus)

#: The stable span-name vocabulary documented above.
PHASES = (
    "map-dfg", "static-prepass", "schedule", "conflict-build", "certify",
    "portfolio-init", "portfolio", "portfolio-device", "repair",
    "validate", "exact-csp",
    "race", "race-side", "comap-region", "arbitrate", "merge-replay",
)

#: The stable flight-event vocabulary documented above (the flight
#: analogue of ``PHASES`` — every `FlightRecorder.emit` kind in the
#: engine and serve tier is one of these).
EVENTS = (
    "phase-begin", "phase-end", "attempt", "static-skip", "certificate",
    "harvest-round", "validate-reject", "cancelled",
    "race-cancel", "race-winner", "comap-round", "comap-arbitrate",
    "serve-admit", "serve-reject", "serve-crash",
)

__all__ = [
    "Counter", "MetricsRegistry", "NullCounter", "NULL_COUNTER",
    "Tracer", "NullTracer", "NULL_TRACER", "SpanRecord", "live",
    "to_json", "from_json", "to_chrome_trace", "write_chrome_trace",
    "write_json", "PHASES",
    "FlightRecorder", "NullFlightRecorder", "NULL_RECORDER",
    "FlightEvent", "recording", "EVENTS",
    "ExplainReport", "explain_result",
    "AccessLog", "ACCESS_LOG_FIELDS", "head_sample",
    "render_prometheus", "parse_prometheus",
]
