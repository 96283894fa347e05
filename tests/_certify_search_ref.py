"""Frozen reference for `repro.core.certify._search_complete`: the
stage-3 exact CSP over unpacked 0/1 adjacency rows, an ``int16`` banned
stack and numpy MRV counts, kept only as the oracle of
``tests/test_certify_bitmask.py``.

It is the search as it ran before the bitmask rewrite, unchanged: its
verdict, placements and node count are its return value, and its orbit
skips are counted through ``tracer`` as ``certify.orbit_skips``.  Do not
optimise or fix it: the point is that the program's version gives the
same answers.
"""

from __future__ import annotations

import numpy as np

from repro.core.certify import _symmetry_attrs
from repro.core.cgra import CGRAConfig
from repro.core.conflict import ConflictGraph
from repro.core.mis import ROW_CACHE_LIMIT
from repro.obs.trace import live

_PLAIN_NODES_FIRST = 4096


def search_complete_ref(cg: ConflictGraph, node_budget: int,
                     row_cache: np.ndarray | None = None,
                     cgra: CGRAConfig | None = None,
                     n_solutions: int = 1,
                     row_cache_limit: int | None = None,
                     on_solution=None, cancel=None, tracer=None,
                     ) -> tuple[bool | None, list[np.ndarray], int]:
    """Stage 3: exact bounded CSP.  Returns (verdict, placements, nodes):
    verdict False = proven infeasible, True = ``placements`` holds up to
    ``n_solutions`` distinct complete independent placements (bool [n]
    memberships, found by continuing the backtracking past the first
    hit), None = budget exhausted before either outcome.

    Enumerating several placements is what closes the residual slow
    path in `map_dfg`: when the validator rejects the first placement's
    bus packing, the next candidates are already in hand — the search
    yields them for a few extra nodes — instead of falling back to the
    full portfolio.

    ``on_solution`` turns the enumeration into an online decision
    procedure (the exact backend's mode, `repro.exact`): each complete
    placement is handed to the callback as a bool [n] membership; a
    True return accepts it and stops the search (verdict True, the
    placement recorded), a False return discards it and the search
    *continues exhausting the space*.  Exhaustion with every placement
    discarded is verdict False: no complete conflict-free placement the
    callback accepts exists.  Under the symmetry-pruned pass that claim
    extends to the full space only when the callback is equivariant
    under the verified row/column automorphisms — `validate_mapping`
    is (it reads row/column indices only as labels, and its restart
    RNG sequence is index-independent), which is what lets the exact
    backend treat an all-rejected exhaustion as UNSAT.

    ``cancel`` (a `core.cancel.CancelToken`) is polled every 64 nodes;
    a cancelled search returns verdict None (unknown), never a proof.
    """
    n = cg.n
    ops = sorted(cg.op_vertices)
    k = len(ops)
    if k == 0:
        return True, [np.zeros(0, dtype=bool)], 0
    # Unpacked rows: share the caller's cache, or materialise one only
    # within the engine's cache bound; past it fall back to per-move
    # row unpack (O(n/8) per expansion, no n^2 allocation).  uint8 rows
    # add directly into the int16 banned stack — no widened copy.
    cache_limit = ROW_CACHE_LIMIT if row_cache_limit is None \
        else row_cache_limit
    if row_cache is not None:
        u8 = row_cache
    elif 0 < n * n <= cache_limit:
        u8 = cg.bits.rows_u8(np.arange(n))
    else:
        u8 = None

    def row(v: int) -> np.ndarray:
        return u8[v] if u8 is not None else cg.bits.row_u8(v)

    op_code = np.empty(n, dtype=np.int64)
    doms = []
    offsets = np.empty(k, dtype=np.int64)
    for i, o in enumerate(ops):
        ids = np.asarray(cg.op_vertices[o], dtype=np.int64)
        op_code[ids] = i
        doms.append(ids)
        offsets[i] = ids[0] if ids.size else 0
    # build_conflict_graph lays candidates out op-contiguously, which
    # turns the per-op alive counts into one reduceat; fall back to
    # bincount for graphs assembled differently.
    contiguous = (all(d.size and (np.diff(d) == 1).all() for d in doms)
                  and (np.diff(offsets) > 0).all() and offsets[0] == 0
                  and doms[-1][-1] == n - 1)
    # MRV tie-break: among equally small domains, expand the op whose
    # candidates are the most constraining (highest mean degree) first —
    # its contradictions surface higher in the tree.  Empirically this
    # cuts the exhaustion on the tight BusMap II=MII instances by 1-2
    # orders of magnitude versus plain MRV.
    tb = np.array([float(np.bitwise_count(cg.bits.rows[d]).sum())
                   / max(d.size, 1) for d in doms])
    tb = -0.9 * tb / (tb.max() + 1.0)
    # Orbit-pruning hits, accumulated locally (one list append per skip
    # would be tracer traffic inside the node loop; one count at the
    # end is free) and published as the `certify.orbit_skips` counter.
    orbit_skips = [0]

    def run(sym: tuple | None, budget: int,
            ) -> tuple[bool | None, list[np.ndarray], int]:
        unassigned = np.ones(k, dtype=bool)
        chosen = np.full(k, -1, dtype=np.int64)
        stack = np.zeros((k + 2, n), dtype=np.int16)
        nodes = [0]
        solutions: list[np.ndarray] = []

        def dfs(depth: int, used_rows: frozenset,
                used_cols: frozenset) -> bool | None:
            nodes[0] += 1
            if nodes[0] > budget:
                return None
            if cancel is not None and not nodes[0] & 63 \
                    and cancel.is_set():
                return None
            if not unassigned.any():
                if on_solution is not None:
                    # Online mode: accept (stop) or discard (keep
                    # exhausting) — see the docstring's UNSAT claim.
                    memb = np.zeros(n, dtype=bool)
                    memb[chosen[chosen >= 0]] = True
                    if on_solution(memb):
                        solutions.append(chosen.copy())
                        return True
                    return False
                # Complete placement: record it and keep backtracking
                # (returning False) until the requested count is in hand.
                solutions.append(chosen.copy())
                return len(solutions) >= n_solutions
            banned = stack[depth]
            alive = banned == 0
            if contiguous:
                counts = np.add.reduceat(alive,
                                         offsets).astype(np.float64)
            else:
                counts = np.bincount(op_code[alive],
                                     minlength=k).astype(np.float64)
            counts += tb
            counts[~unassigned] = np.inf
            i = int(np.argmin(counts))
            if counts[i] < 0.0:
                return False
            unassigned[i] = False
            dom = doms[i]
            seen: set = set()
            result: bool | None = False
            for v in dom[alive[dom]]:
                nur, nuc = used_rows, used_cols
                if sym is not None:
                    # Orbit representative: under the stabilizer of the
                    # partial assignment (which references only used
                    # rows/cols), all still-unused rows are
                    # interchangeable, and likewise columns — one
                    # candidate per (drive-kind, row-or-fresh,
                    # col-or-fresh) key suffices.
                    vrow, vcol, vdrv = sym
                    r_ref, c_ref = int(vrow[v]), int(vcol[v])
                    key = (int(vdrv[v]),
                           r_ref if r_ref < 0 or r_ref in used_rows
                           else -2,
                           c_ref if c_ref < 0 or c_ref in used_cols
                           else -2)
                    if key in seen:
                        orbit_skips[0] += 1
                        continue
                    seen.add(key)
                    if r_ref >= 0:
                        nur = used_rows | {r_ref}
                    if c_ref >= 0:
                        nuc = used_cols | {c_ref}
                chosen[i] = v
                np.add(banned, row(v), out=stack[depth + 1])
                r = dfs(depth + 1, nur, nuc)
                if r is None or r:
                    result = r
                    break
            else:
                chosen[i] = -1
            unassigned[i] = True
            return result

        verdict = dfs(0, frozenset(), frozenset())
        return verdict, solutions, nodes[0]

    # Phase 1: plain search under a small budget — feasible schedules
    # usually resolve here, skipping the symmetry verification cost.
    # Graphs past the row-cache bound stop here too: without the u8
    # cache every node pays an O(n) row unpack and the symmetry
    # verification (which needs the full cache) is unavailable, so a
    # six-figure node budget burns seconds per (II, jitter) with no
    # realistic chance of exhausting a |V_C| ~ 10^4 space — "unknown"
    # after the cheap pass is the honest verdict at that scale.
    budget1 = min(node_budget, _PLAIN_NODES_FIRST)
    verdict, sols, spent = run(None, budget1)
    if verdict is None and not sols and node_budget > budget1 \
            and u8 is not None:
        sym = _symmetry_attrs(cg, cgra, u8) if u8 is not None else None
        verdict, sols, spent2 = run(sym, node_budget - spent)
        spent += spent2
    placements = []
    for chosen in sols:
        p = np.zeros(n, dtype=bool)
        p[chosen[chosen >= 0]] = True
        placements.append(p)
    if placements:
        # An exhausted (False) or budget-out (None) sweep that still
        # recorded placements is a feasibility witness, not a proof.
        verdict = True
    trc = live(tracer)
    trc.count("certify.csp_nodes", spent)
    trc.count("certify.orbit_skips", orbit_skips[0])
    return verdict, placements, spent
