"""Exact-search nodes per request of the traced window: the
``certify.csp_nodes`` counts of the ``certify`` spans (core/certify.py
`_search_complete` counts its nodes once per call).  None when no span
counted ``certify.csp_nodes``."""

from benchkit.counts import span_totals


def read(run):
    got = span_totals(run)
    if got is None or "certify.csp_nodes" not in got[0]:
        return None
    totals, n = got
    return totals["certify.csp_nodes"] / n
