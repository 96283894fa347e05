"""Self time per request, in ms, of the ``conflict-build`` spans: the
conflict-graph build (core/conflict.py)."""

from benchkit.layers import self_ms_per_request


def read(run):
    return self_ms_per_request(run, "conflict-build")
