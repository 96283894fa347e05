"""Ejection-chain search nodes per request of the traced window: the
``repair.nodes`` counts of the ``repair`` spans (core/mis.py
`ejection_repair` counts its nodes once per call).  None when no span
counted ``repair.nodes``."""

from benchkit.counts import span_totals


def read(run):
    got = span_totals(run)
    if got is None or "repair.nodes" not in got[0]:
        return None
    totals, n = got
    return totals["repair.nodes"] / n
