"""Ejection-chain repair tries per request of the traced window: the
``repair.tries`` counts of the ``repair`` spans (core/bandmap.py, one per
`ejection_repair` call)."""

from benchkit.counts import span_totals


def read(run):
    got = span_totals(run)
    if got is None:
        return None
    totals, n = got
    return totals.get("repair.tries", 0) / n
