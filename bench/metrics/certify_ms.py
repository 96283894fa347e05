"""Self time per request, in ms, of the ``certify`` spans: the
certificate search (core/certify.py)."""

from benchkit.layers import self_ms_per_request


def read(run):
    return self_ms_per_request(run, "certify")
