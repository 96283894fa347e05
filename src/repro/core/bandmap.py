"""The BandMap pipeline (paper Fig. 3): scheduling with bandwidth allocation
→ routing-resource pre-allocation → binding by MIS on the mixed conflict
graph → incomplete-mapping processing.

`map_dfg(..., mode="busmap")` runs the same pipeline with the BusMap
baseline policy (one port per datum, routing-PE broadcast), which is the
paper's comparison target.

One (II, jitter) walk (`_schedules`) serves both mapping backends; each
adds its own search per schedule: the portfolio (`_map_dfg_portfolio`,
`_harvest`) here, the complete prover in `repro.exact.backend`.  Every
`MappingResult` either returns is built by `_result`.
"""

from __future__ import annotations

import dataclasses
import time as _time
from typing import NamedTuple

import numpy as np

from repro.obs.flight import recording
from repro.obs.trace import live

from .certify import IICertificate, certify_ii_infeasible
from .cgra import CGRAConfig
from .conflict import (ConflictGraph, Vertex, build_conflict_graph,
                       constructive_init)
from .dfg import DFG
from .mis import (ROW_CACHE_LIMIT, PortfolioSBTS, ejection_repair,
                  mis_indices)
from .options import MapOptions
from .schedule import ScheduledDFG, mii, schedule_dfg
from .validate import ValidationReport, validate_mapping


@dataclasses.dataclass
class MappingResult:
    ok: bool
    mode: str
    ii: int
    mii: int
    n_routing_pes: int
    ports_per_vio: dict[int, int]
    placement: dict[int, Vertex]
    sched: ScheduledDFG | None
    report: ValidationReport | None
    cg_size: tuple[int, int]      # (|V_C|, |E_C|)
    mis_size: int
    n_ops: int
    attempts: int
    wall_s: float
    # II-infeasibility certificates collected along the way (one per
    # (II, jitter) combination proven unbindable and skipped).
    certificates: list[IICertificate] = dataclasses.field(
        default_factory=list)
    # Set by the exact backend (`repro.exact`).  ``optimal`` marks an
    # ok=True result whose II is proven minimal: every lower
    # (II, jitter) combination from MII up carries a certificate (MII
    # itself is a sound absolute lower bound, so the claim is absolute
    # at II=MII and relative to the engine's deterministic schedule
    # family above it).  ``proved_infeasible`` marks an ok=False result
    # where *every* (II, jitter) combination up to ``max_ii`` was
    # certified unbindable — the sound negative the serve cache admits
    # even when validation attempts were spent along the way.
    # ``backend`` records which engine produced the result
    # ("portfolio" | "exact" | "race:portfolio" | "race:exact").
    optimal: bool = False
    proved_infeasible: bool = False
    backend: str = "portfolio"
    # Flight-recorder dump (JSON-able event dicts, `repro.obs.flight`)
    # attached by `map_dfg` to every ok=False result mapped under a
    # live recorder — the last-N structured events (attempts,
    # certificates, harvest coverage, cancel) a postmortem needs
    # without a traced re-run.  Empty on successes and `record=None`
    # runs, so the common positive path stays lean.
    flight: tuple = ()

    @property
    def ii_ratio(self) -> float:
        """MII / II — the paper's throughput metric (1.0 = best)."""
        return self.mii / self.ii if self.ii else 0.0

    # ------------------------------------------------- serialization
    # Everything a MappingResult holds (ScheduledDFG, Vertex placement,
    # ValidationReport, IICertificate) is plain dataclasses + numpy, so
    # pickle round-trips it exactly; the version tag guards the serving
    # cache's on-disk artifacts (`serve.cache`) against silently loading
    # results written by an incompatible result layout.
    # v2: optimal / proved_infeasible / backend fields (exact backend).
    # v3: flight field (obs flight-recorder dump on failed results).
    SERIAL_VERSION = 3

    def to_bytes(self) -> bytes:
        import pickle
        return pickle.dumps((MappingResult.SERIAL_VERSION, self),
                            protocol=pickle.HIGHEST_PROTOCOL)

    @staticmethod
    def from_bytes(data: bytes) -> "MappingResult":
        import pickle
        version, res = pickle.loads(data)
        if version != MappingResult.SERIAL_VERSION:
            raise ValueError(
                f"MappingResult serial version {version} != "
                f"{MappingResult.SERIAL_VERSION}")
        return res

    def summary(self) -> str:
        return (f"{self.mode}: II={self.ii} (MII={self.mii}, "
                f"ratio={self.ii_ratio:.2f}), routingPEs={self.n_routing_pes}, "
                f"|V_C|={self.cg_size[0]}, |E_C|={self.cg_size[1]}, "
                f"ok={self.ok}")

    def explain(self, *, tracer=None, flight=None):
        """Narrated report of *why* the mapping landed here: the II
        escalation path with per-II cause (static floor / certificate
        stage / portfolio exhaustion), routing-PE accounting, coverage
        curve and race outcome.  Returns `repro.obs.ExplainReport`;
        pass the run's ``tracer`` for the coverage/kick detail (the
        result alone carries certificates and any attached flight
        dump).  Imported lazily — `repro.obs.explain` must not be a
        dependency of constructing results."""
        from repro.obs.explain import explain_result
        return explain_result(self, tracer=tracer, flight=flight)


def map_dfg(dfg: DFG, cgra: CGRAConfig,
            options: "MapOptions | dict | None" = None, *,
            cancel=None, tracer=None, record=None,
            **kwargs) -> MappingResult:
    """Run the full 4-phase mapping.  Phase 4 (incomplete-mapping
    processing) is the retry loop of Fig. 3, one walk over (II, jitter)
    schedules: II escalates from MII once jitters 0-3 all fail (ASAP
    schedules are II-invariant, so jitter supplies the diversity).  Per
    schedule the portfolio backend certifies, validates the certificate
    search's complete placements, then harvests a multi-seed SBTS
    portfolio with fresh-seed restarts; ``backend="exact"`` runs the
    complete prover on the same walk and ``"race"`` runs both
    (`repro.exact`).

    ``options`` is a `core.options.MapOptions` (the single source of
    every knob), a plain option dict (the serve tier's wire format,
    e.g. ``{"mode": "busmap", "max_ii": 8}``), or legacy keywords
    (``mode="busmap", max_ii=8``); dicts and keywords go through
    `MapOptions.from_kwargs`, whose `core.options.LEGACY_KNOBS` maps
    legacy names onto groups and drops unknown keys with a warning.
    Knob highlights: ``certify`` runs the certificate stages before the
    portfolio; ``bus_pressure`` folds provable capacity edges (bus
    cells, LRF pairs) into the conflict graph; ``static_prepass`` skips
    statically-doomed IIs via the schedule-free demand analysis;
    ``min_ii`` floors the walk (the co-mapper's common-II handle);
    ``row_cache_limit`` bounds the unpacked-row caches in bytes;
    ``max_bus_fanout`` caps consumers per delivery port; ``group_move``
    enables the clustered kick neighbourhood (`mis.GroupMoveConfig`);
    ``engine`` is ``"numpy"`` (`mis.PortfolioSBTS`, default) or
    ``"device"`` (`core.mis_device.DeviceSBTS`: ``device_seeds``
    trajectories through the `kernels.sbts_step` Pallas kernel,
    interpret mode on CPU backends, rounds traced as
    "portfolio-device").

    ``cancel`` (`core.cancel.CancelToken`), ``tracer``
    (`repro.obs.Tracer`: a span tree rooted at "map-dfg", taxonomy in
    `repro.obs`) and ``record`` (`repro.obs.FlightRecorder`: a bounded
    ring of structured events, whose `dump()` every ``ok=False``
    result carries as ``result.flight``) are runtime handles, never
    knobs in `MapOptions.fingerprint` (the serve cache key).  The
    cancel is polled before each II and each jitter, between harvest
    rounds and inside the portfolio's iteration loop; a cancelled run
    returns its best-effort ``ok=False`` result.  All three default to
    None, which leaves the run bit-identical (the
    ``tracer-default-none`` / ``recorder-default-none`` lint rules)."""
    opts = MapOptions.coerce(options, kwargs)
    if opts.backend != "portfolio":
        from repro.exact import exact_map_dfg, race_map_dfg
        if opts.backend == "exact":
            return exact_map_dfg(dfg, cgra, options=opts, cancel=cancel,
                                 tracer=tracer)
        if opts.backend == "race":
            return race_map_dfg(dfg, cgra, options=opts, cancel=cancel,
                                tracer=tracer, record=record)
        raise ValueError(f"unknown mapping backend {opts.backend!r}")
    rec = recording(record)
    rec.emit("phase-begin", phase="map-dfg", mode=opts.mode,
             n_ops=len(dfg.ops))
    with live(tracer).span("map-dfg", mode=opts.mode,
                           n_ops=len(dfg.ops)) as sp:
        res = _map_dfg_portfolio(dfg, cgra, opts, cancel=cancel,
                                 tracer=tracer, record=record)
        sp.set(ok=res.ok, ii=res.ii, attempts=res.attempts)
    rec.emit("phase-end", phase="map-dfg", ok=res.ok, ii=res.ii,
             attempts=res.attempts)
    if record is not None:
        # Failed results carry their postmortem; successes stay lean.
        if not res.ok:
            res = dataclasses.replace(res, flight=record.dump())
    return res


@dataclasses.dataclass
class _Walk:
    """One map's bookkeeping: the walk, its searches and `_result`."""
    opts: MapOptions
    the_mii: int
    t_start: float = dataclasses.field(default_factory=_time.perf_counter)
    certificates: list[IICertificate] = dataclasses.field(
        default_factory=list)
    attempts: int = 0
    cancelled: bool = False     # a poll of the walk saw the cancel

    @property
    def cache_limit(self) -> int:
        limit = self.opts.portfolio.row_cache_limit
        return ROW_CACHE_LIMIT if limit is None else limit


class _Candidate(NamedTuple):
    """The newest binding a search reached: a validated or rejected
    placement (``report`` set), or a shortfall of ``size`` ops."""
    sched: ScheduledDFG | None = None
    placement: dict | None = None
    report: ValidationReport | None = None
    size: int = 0
    cg_size: tuple[int, int] = (0, 0)


def _result(walk: _Walk, cand: _Candidate, **claims) -> MappingResult:
    """The one place either backend builds a `MappingResult`.  ``ok`` is
    the validator's verdict on ``cand``, so every positive is
    validator-backed; ``claims`` are the backend's own ``optimal`` /
    ``proved_infeasible`` / ``backend``."""
    sched, placement, report, size, cg_size = cand
    return MappingResult(
        ok=report is not None and report.ok, mode=walk.opts.mode,
        ii=sched.ii if sched else -1, mii=walk.the_mii,
        n_routing_pes=sched.n_routing_ops if sched else 0,
        ports_per_vio=dict(sched.ports_allocated) if sched else {},
        placement=placement or {}, sched=sched, report=report,
        cg_size=cg_size, mis_size=size,
        n_ops=len(sched.dfg.ops) if sched else 0, attempts=walk.attempts,
        wall_s=_time.perf_counter() - walk.t_start,
        certificates=walk.certificates, **claims)


def _schedules(dfg: DFG, cgra: CGRAConfig, walk: _Walk, *, prepass: bool,
               cancel, tracer=None, record=None):
    """The (II, jitter) walk both backends run: II from max(MII,
    ``min_ii``) to ``max_ii``, jitters 0-3 at each, yielding ``(ii,
    jitter, sched, cg)`` outside any open span.  ``prepass`` floors the
    walk with the schedule-free demand bound, adding a
    ``static-demand`` certificate to ``walk`` per II skipped.  A
    combination the scheduler emits nothing at is skipped: there is no
    schedule to bind, so it is decided, not unknown."""
    trc, rec = live(tracer), recording(record)
    sch = walk.opts.schedule
    static_floor, static_detail = walk.the_mii, ""
    if prepass:
        from repro.analysis.demand import implied_demand_bounds
        rec.emit("phase-begin", phase="static-prepass", mii=walk.the_mii)
        with trc.span("static-prepass", mii=walk.the_mii) as ssp:
            for b in implied_demand_bounds(
                    dfg, cgra, max_bus_fanout=sch.max_bus_fanout):
                if b.min_ii > static_floor:
                    static_floor, static_detail = b.min_ii, b.summary()
            ssp.set(floor=static_floor)
        rec.emit("phase-end", phase="static-prepass", floor=static_floor)
    for cur_ii in range(max(walk.the_mii, sch.min_ii or 0), sch.max_ii + 1):
        walk.cancelled = cancel is not None and cancel.is_set()
        if walk.cancelled:
            return
        if cur_ii < static_floor:
            # Unbindable at every jitter (jitter=-1 marks the
            # whole-slice claim): no schedule, no search.
            walk.certificates.append(IICertificate(
                ii=cur_ii, jitter=-1, stage="static-demand",
                detail=static_detail, nodes=0, wall_s=0.0))
            rec.emit("static-skip", ii=cur_ii, floor=static_floor)
            continue
        for jitter in (0, 1, 2, 3):
            walk.cancelled = cancel is not None and cancel.is_set()
            if walk.cancelled:
                return
            rec.emit("attempt", ii=cur_ii, jitter=jitter)
            try:
                with trc.span("schedule", ii=cur_ii, jitter=jitter):
                    sched = schedule_dfg(
                        dfg, cgra, mode=walk.opts.mode, ii=cur_ii,
                        max_ii=cur_ii, use_grf=sch.use_grf,
                        jitter=jitter, seed=walk.opts.seed,
                        max_bus_fanout=sch.max_bus_fanout, tracer=tracer)
            except RuntimeError:
                continue
            yield cur_ii, jitter, sched, build_conflict_graph(
                sched, cgra, bus_pressure=walk.opts.bus_pressure,
                tracer=tracer)


def _validate(sched: ScheduledDFG, cgra: CGRAConfig, cg: ConflictGraph,
              sol: np.ndarray, size: int, source: str, *, tracer=None,
              record=None) -> _Candidate:
    """Replay one complete conflict-free solution on the validator."""
    trc = live(tracer)
    placement = {cg.vertices[i].op: cg.vertices[i]
                 for i in mis_indices(sol)}
    with trc.span("validate", ii=sched.ii, source=source):
        report = validate_mapping(sched, cgra, placement)
        trc.count("validate.calls")
        if not report.ok:
            trc.count("validate.rejects")
            recording(record).emit("validate-reject", ii=sched.ii,
                                   source=source)
    return _Candidate(sched, placement, report, size, (cg.n, cg.n_edges))


def _map_dfg_portfolio(dfg: DFG, cgra: CGRAConfig, opts: MapOptions, *,
                       cancel, tracer=None, record=None) -> MappingResult:
    """The II loop of the portfolio backend: per schedule, certify,
    validate the CSP's solutions, then `_harvest`."""
    rec = recording(record)
    ct = opts.certify
    walk = _Walk(opts, mii(dfg, cgra))
    last = _Candidate()
    for cur_ii, jitter, sched, cg in _schedules(
            dfg, cgra, walk, prepass=ct.static_prepass, cancel=cancel,
            tracer=tracer, record=record):
        # Memoized on the graph: certify and the portfolio share it.
        shared_u8 = cg.row_cache(walk.cache_limit)
        if ct.enabled:
            cert, csp_sols = certify_ii_infeasible(
                cg, sched, cgra, jitter=jitter,
                node_budget=ct.budget, row_cache=shared_u8,
                n_placements=ct.n_exact_placements,
                row_cache_limit=walk.cache_limit, cancel=cancel,
                tracer=tracer)
            if cert is not None:
                # Proven unbindable: skip the whole portfolio budget
                # for this (II, jitter) combination.
                walk.certificates.append(cert)
                rec.emit("certificate", ii=cur_ii, jitter=jitter,
                         stage=cert.stage, nodes=cert.nodes)
                if last.sched is None:
                    last = _Candidate(sched, cg_size=(cg.n, cg.n_edges))
                continue
            # The exhaustive stage's complete conflict-free placements
            # go to the validator before the portfolio runs (several:
            # bus packing / LRF residency can reject the first).
            for csp_sol in csp_sols or ():
                walk.attempts += 1
                last = _validate(sched, cgra, cg, csp_sol,
                                 len(sched.dfg.ops), "csp",
                                 tracer=tracer, record=record)
                if last.report.ok:
                    return _result(walk, last)
        last = _harvest(sched, cg, shared_u8, cgra, walk, jitter,
                        cancel=cancel, tracer=tracer,
                        record=record) or last
        if last.report is not None and last.report.ok:
            return _result(walk, last)
    if cancel is not None and cancel.is_set():
        rec.emit("cancelled", ii=last.sched.ii if last.sched else -1)
    # attempts == 0 with certificates attached: every combination that
    # scheduled was proven unbindable before any stochastic search ran,
    # a full-range UNSAT proof, unless a cancel cut the walk short.
    proved = bool(walk.certificates) and walk.attempts == 0 \
        and not (cancel is not None and cancel.is_set())
    return _result(walk, last, proved_infeasible=proved)


def _harvest(sched: ScheduledDFG, cg: ConflictGraph, shared_u8,
             cgra: CGRAConfig, walk: _Walk, jitter: int, *, cancel,
             tracer=None, record=None) -> _Candidate | None:
    """Multi-seed SBTS portfolio on one schedule, in harvest rounds:
    validate each distinct complete solution (small shortfalls repaired
    first); when the validator rejects all (bus congestion the graph's
    capacity edges cannot prove, LRF overflow that takes three or more
    ops or depends on drive timing), re-arm or re-seed the
    complete seeds until the iteration budget is spent.  Returns the
    first validated candidate, else the last tried (None if none)."""
    trc, rec = live(tracer), recording(record)
    pf = walk.opts.portfolio
    device_engine = pf.engine == "device"
    n_ops = len(sched.dfg.ops)
    # Spend extra effort at II = MII: throughput is the top concern
    # (paper §III-A), so a success there dominates any II+1 mapping.
    budget = pf.restarts * (2 if sched.ii == walk.the_mii else 1)
    base = walk.opts.seed * 1001 + sched.ii * 131 + jitter * 31
    op_of = cg.op_of
    with trc.span("portfolio-init", ii=sched.ii, jitter=jitter,
                  seeds=budget, engine=pf.engine):
        inits = [constructive_init(cg, sched, cgra, seed=base + k)
                 if k % 3 != 2 else None for k in range(budget)]
        walk.attempts += budget
        if device_engine:
            # The same warm starts, fanned out to `device_seeds`
            # trajectories on the accelerator (interpret mode on CPU).
            from .mis_device import DeviceSBTS
            sbts = DeviceSBTS(cg.bits, inits, k=pf.device_seeds, seed=base)
        else:
            sbts = PortfolioSBTS(cg.bits, inits, seed=base,
                                 row_cache=shared_u8,
                                 row_cache_limit=walk.cache_limit,
                                 op_of=op_of, group_move=pf.group_move)
    last = None
    seen_sols: set[bytes] = set()
    remaining = pf.iters
    fresh = budget
    for rnd in range(4 * budget):
        if cancel is not None and cancel.is_set():
            break
        start_it = sbts.it
        with trc.span("portfolio-device" if device_engine else "portfolio",
                      ii=sched.ii, jitter=jitter, round=rnd) as psp:
            bests = sbts.run(remaining, target=n_ops, cancel=cancel,
                             tracer=tracer)
            best_cov = int(sbts.best_size.max()) if sbts.k else 0
            psp.set(iters=sbts.it - start_it, best=best_cov,
                    coverage=best_cov / n_ops if n_ops else 1.0)
            if device_engine:
                psp.set(interpret=sbts.interpret)
        rec.emit("harvest-round", ii=sched.ii, jitter=jitter, round=rnd,
                 best=best_cov, coverage=best_cov / n_ops if n_ops else 1.0)
        remaining -= sbts.it - start_it
        for k in np.argsort(-bests.sum(axis=1), kind="stable"):
            sol = bests[k].copy()
            key = sol.tobytes()
            if key in seen_sols:
                # Seeds often converge to the same best set; repairing
                # duplicates wastes the ejection budget.
                continue
            seen_sols.add(key)
            if 0 < n_ops - int(sol.sum()) <= 4:
                sol = _repair(cg, sol, op_of, n_ops, sched.ii,
                              base + rnd * 97 + int(k), tracer=tracer)
            size = int(sol.sum())
            if size < n_ops:
                last = _Candidate(sched, size=size, cg_size=(cg.n, cg.n_edges))
                continue
            last = _validate(sched, cgra, cg, sol, size, "portfolio",
                             tracer=tracer, record=record)
            if last.report.ok:
                return last
        if remaining <= 0:
            break
        # Alternate a local diversification with a fresh restart (the
        # paper's independent-restart retry) over the harvested seeds.
        complete = np.flatnonzero(sbts.best_size >= n_ops)
        if device_engine:
            # Hundreds of ~1000 device trajectories may converge in a
            # round; re-seeding the top 16 keeps the pattern at bounded
            # host cost (a constructive_init per re-seed).
            complete = complete[:16]
        for j, k in enumerate(complete):
            if j % 2 == 0:
                sbts.rearm(int(k))
            else:
                fresh += 1
                sbts.reset_seed(int(k), constructive_init(
                    cg, sched, cgra, seed=base + fresh))
    return last


def _repair(cg: ConflictGraph, sol: np.ndarray, op_of: np.ndarray,
            n_ops: int, cur_ii: int, rs: int, *, tracer=None) -> np.ndarray:
    """Ejection-chain repair of a small shortfall: up to six seeded
    tries (multi-seed: candidate order is randomised, so retries
    differ); the first that covers every op, else the last."""
    trc = live(tracer)
    with trc.span("repair", ii=cur_ii, shortfall=n_ops - int(sol.sum())):
        for rk in range(6):
            fixed = ejection_repair(cg.bits, sol, cg.op_vertices, op_of,
                                    depth=4, seed=rs * 13 + rk,
                                    masks=cg.nbr_masks(), tracer=tracer)
            trc.count("repair.tries")
            if int(fixed.sum()) >= n_ops:
                trc.count("repair.fixed")
                break
    return fixed


def compare_modes(dfg: DFG, cgra: CGRAConfig, *, seed: int = 0,
                  **kw) -> dict[str, MappingResult]:
    """BandMap vs BusMap on the same DFG/CGRA — the paper's experiment."""
    return {m: map_dfg(dfg, cgra, mode=m, seed=seed, **kw)
            for m in ("bandmap", "busmap")}
