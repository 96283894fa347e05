"""Cycles a staggered VIO operand waits in its consumer's LRF, on
average over the schedules emitted: ``schedule.hold_cycles`` over
``schedule.staggered``, both counted on the ``schedule`` spans; None
when no span counted a staggered operand."""

from benchkit.counts import span_totals


def read(run):
    got = span_totals(run)
    if got is None or not got[0].get("schedule.staggered"):
        return None
    totals = got[0]
    return totals.get("schedule.hold_cycles", 0) / totals["schedule.staggered"]
