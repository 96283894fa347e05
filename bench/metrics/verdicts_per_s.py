"""Verdicts (bindings and infeasibility proofs) per second over the whole
window: whole cycles of the traffic mix, none started after the window's
nominal length, divided by the time to the last verdict."""

from benchkit.stats import window_rate


def read(run):
    return window_rate(sum(r.answered for r in run.records), run.t_start,
                       run.t_last)
