"""95th percentile (nearest rank) of the time from submission to verdict
over all requests of the window; a request without a verdict counts as
slower than every other."""

from benchkit.stats import p95_all


def read(run):
    return p95_all([r.latency_s for r in run.records])
