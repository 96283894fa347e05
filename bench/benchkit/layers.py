"""Per-layer numbers from the program's spans and the device trace."""

from __future__ import annotations


def self_times(spans) -> dict[int, float]:
    """Span id -> its duration less the part its direct children cover
    (children of one span run in its thread, one after another)."""
    child: dict[int, float] = {}
    for sp in spans:
        if sp.parent >= 0:
            child[sp.parent] = child.get(sp.parent, 0.0) + sp.t1 - sp.t0
    return {sp.sid: sp.t1 - sp.t0 - child.get(sp.sid, 0.0) for sp in spans}


def self_ms_per_request(run, name: str) -> float | None:
    """Mean self time, in ms, of the spans called ``name`` per request of
    the traced window; None when no request ran such a span."""
    recs = [r for r in run.records if r.spans is not None]
    total, seen = 0.0, False
    for r in recs:
        own = self_times(r.spans)
        for sp in r.spans:
            if sp.name == name:
                total += own[sp.sid]
                seen = True
    if not seen:
        return None
    return 1e3 * total / len(recs)
