"""LRF-pressure vertex pairs per request of the traced window: the
``conflict.lrf_edges`` counts of the ``conflict-build`` spans
(core/conflict.py `lrf_pressure_edges`, counted once per build that adds
any).  None when no span counted ``conflict.lrf_edges``."""

from benchkit.counts import span_totals


def read(run):
    got = span_totals(run)
    if got is None or "conflict.lrf_edges" not in got[0]:
        return None
    totals, n = got
    return totals["conflict.lrf_edges"] / n
