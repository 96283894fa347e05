"""Per-layer numbers from the counts the program's spans carry.

A traced request's spans each carry ``counts``: the counter increments
made while the span was the innermost one open, so the sum over a
request's spans is the request's total.  A program whose spans carry no
``counts`` gives no number.
"""

from __future__ import annotations


def span_totals(run) -> tuple[dict, int] | None:
    """(counter name -> total over the spans of the traced requests,
    number of traced requests); None when no traced request has a span
    that carries counts."""
    recs = [r for r in run.records if r.spans is not None]
    spans = [sp for r in recs for sp in r.spans]
    if not any(hasattr(sp, "counts") for sp in spans):
        return None
    totals: dict = {}
    for sp in spans:
        for name, n in getattr(sp, "counts", {}).items():
            totals[name] = totals.get(name, 0) + n
    return totals, len(recs)


def share(run, part: str, whole: str) -> float | None:
    """100 * total ``part`` / total ``whole``; None when ``whole`` was
    never counted."""
    got = span_totals(run)
    if got is None or not got[0].get(whole):
        return None
    totals = got[0]
    return 100.0 * totals.get(part, 0) / totals[whole]
