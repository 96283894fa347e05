"""Share, in %, of the VIO operands of compute ops, over the schedules
emitted, that are delivered early because their op reads two or more
VIOs: ``schedule.staggered`` over ``schedule.vio_operands``, both
counted on the ``schedule`` spans; None when no span counted operands."""

from benchkit.counts import share


def read(run):
    return share(run, "schedule.staggered", "schedule.vio_operands")
