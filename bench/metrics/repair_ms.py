"""Self time per request, in ms, of the ``repair`` spans: ejection-chain
repair of near-complete sets (core/mis.py)."""

from benchkit.layers import self_ms_per_request


def read(run):
    return self_ms_per_request(run, "repair")
