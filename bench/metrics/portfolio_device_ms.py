"""Self time per request, in ms, of the ``portfolio-device`` spans: the
device SBTS rounds (core/mis_device.py)."""

from benchkit.layers import self_ms_per_request


def read(run):
    return self_ms_per_request(run, "portfolio-device")
