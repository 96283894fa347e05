"""The exact mapping backend: a complete prover over the engine's own
search space.

`exact_map_dfg` runs inside the same (II, jitter) walk as
`bandmap.map_dfg` (`bandmap._schedules`), but replaces the stochastic
portfolio with the certificate machinery run to *decision*:

- **Encoding.**  Per (II, jitter) schedule, the CP/SAT-style model is
  the mixed conflict graph itself: one variable per op over its
  candidate tuples (TIN port tuples, TOUT port tuples, QUAD PE slots,
  routing drives), pairwise constraints = occupancy cliques +
  dependency realizability + `bus_pressure_edges` + `lrf_pressure_edges`
  + the Hall-style joint bus-demand bound (`repro.exact.hall`) folding
  per-(scope, bus, cycle) capacity into the graph.
- **Search.**  `certify._search_complete` with its MRV /
  most-constraining tie-break / forward checking on integer bitmasks
  and the *verified* row/column symmetry-orbit pruning, run in online
  mode: every complete conflict-free placement is handed to
  `validate_mapping` (the engine's single soundness authority —
  concrete bus-instance packing, LRF/GRF residency) as it is found.
  Accept ⇒ SAT for this schedule; exhaustion with every placement
  rejected ⇒ UNSAT for this schedule (sound because the validator is
  equivariant under the fabric's row/column relabelings, so rejecting
  an orbit representative rejects its orbit — asserted in
  tests/test_exact_differential.py).
- **Verdicts.**  The first validator-accepted placement returns
  ``ok=True`` with ``optimal=True`` iff every lower (II, jitter)
  combination was certified UNSAT (or unschedulable): at II = MII the
  claim is absolute (MII is a sound lower bound for *any* modulo
  schedule); above it, it is optimality within the engine's schedule
  family — the exact guarantee the differential tests lean on, since
  the portfolio searches the same family and therefore can never beat
  a proven exact II.  If the whole range up to ``max_ii`` is certified,
  the result is ``ok=False`` with ``proved_infeasible=True`` — the
  certificate-backed negative the serve cache admits.  The family
  staggers the VIO operands of one op over distinct delivery slots
  (`core/schedule.py`), so a kernel whose ops read two memory operands
  is decided on schedules that can bind it.

Budget knobs
------------
``certify.budget`` caps CSP nodes per (II, jitter) combination.  A
combination that exhausts it is *unknown*: the backend keeps escalating
II and can still return a mapping, but drops the ``optimal`` /
``proved_infeasible`` claims — budgets degrade the claim, never the
soundness.  ``cancel`` (`core.cancel.CancelToken`) is polled between
combinations and every few dozen search nodes; a cancelled run returns
a claim-less ``ok=False`` result, which is how the race
(`repro.exact.race`) discards a losing prover mid-search.
"""

from __future__ import annotations

import numpy as np

from repro.core.bandmap import (MappingResult, _Candidate, _result,
                                _schedules, _Walk)
from repro.core.certify import certify_ii_infeasible
from repro.core.cgra import CGRAConfig
from repro.core.dfg import DFG
from repro.core.mis import mis_indices
from repro.core.options import MapOptions
from repro.core.schedule import mii
from repro.core.validate import validate_mapping
from repro.obs.trace import live

from .hall import hall_pressure_edges


class _ValidateSink:
    """`on_solution` callback: validate each complete placement the CSP
    enumerates, keep the first accepted one."""

    def __init__(self, sched, cg, cgra) -> None:
        self.sched, self.cg, self.cgra = sched, cg, cgra
        self.tried = 0
        self.accepted: tuple | None = None

    def __call__(self, memb: np.ndarray) -> bool:
        self.tried += 1
        placement = {self.cg.vertices[i].op: self.cg.vertices[i]
                     for i in mis_indices(memb)}
        report = validate_mapping(self.sched, self.cgra, placement)
        if report.ok:
            self.accepted = (placement, report)
            return True
        return False


def exact_map_dfg(dfg: DFG, cgra: CGRAConfig,
                  options: "MapOptions | dict | None" = None, *,
                  cancel=None, tracer=None, **kwargs) -> MappingResult:
    """Prove the engine-optimal II (or certified infeasibility) for one
    DFG — see the module docstring for the exact claims.  Accepts the
    same `MapOptions` / dict / legacy-keyword forms as `map_dfg` so the
    race can hand both backends the same problem, plus the
    historical ``node_budget`` alias of ``certify.budget``;
    ``certify.hall`` gates the joint bus-demand bound (on by default —
    it only ever strengthens UNSAT proofs)."""
    if "node_budget" in kwargs:
        kwargs = dict(kwargs)
        kwargs["certify_budget"] = kwargs.pop("node_budget")
    opts = MapOptions.coerce(options, kwargs)
    ct = opts.certify
    trc = live(tracer)
    walk = _Walk(opts, mii(dfg, cgra))
    proved_all = True      # every combination below the cursor decided
    last = _Candidate()
    for cur_ii, jitter, sched, cg in _schedules(
            dfg, cgra, walk, prepass=False, cancel=cancel, tracer=tracer):
        if ct.hall:
            hall_pressure_edges(cg.bits, cg.vertices,
                                cg.op_vertices, sched, cgra)
        # Memoized on the graph; hall edges are already folded in, so
        # the cache sees the strengthened adjacency.
        shared_u8 = cg.row_cache(walk.cache_limit)
        n_ops = len(sched.dfg.ops)
        sink = _ValidateSink(sched, cg, cgra)
        with trc.span("exact-csp", ii=cur_ii, jitter=jitter,
                      n_ops=n_ops) as xsp:
            cert, _ = certify_ii_infeasible(
                cg, sched, cgra, jitter=jitter,
                node_budget=ct.budget, row_cache=shared_u8,
                row_cache_limit=walk.cache_limit, on_solution=sink,
                cancel=cancel, tracer=tracer)
            xsp.set(validations=sink.tried,
                    verdict="sat" if sink.accepted is not None
                    else "unsat" if cert is not None else "unknown")
            if cert is not None:
                xsp.set(nodes=cert.nodes)
        trc.count("exact.validations", sink.tried)
        walk.attempts += sink.tried
        if sink.accepted is not None:
            placement, report = sink.accepted
            return _result(walk, _Candidate(
                sched, placement, report, n_ops, (cg.n, cg.n_edges)),
                optimal=proved_all, backend="exact")
        last = _Candidate(sched, cg_size=(cg.n, cg.n_edges))
        if cert is not None:
            walk.certificates.append(cert)
        else:
            # Budget out (or cancelled mid-search): this
            # combination is unknown, every claim past it degrades.
            proved_all = False
    return _result(walk, last, backend="exact",
                   proved_infeasible=proved_all and not walk.cancelled)
