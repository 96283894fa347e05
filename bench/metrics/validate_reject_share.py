"""Share, in %, of complete candidates the program's validator rejects
(bus packing, LRF limits): ``validate.rejects`` over ``validate.calls``,
both counted on the ``validate`` spans; None when nothing was
validated."""

from benchkit.counts import share


def read(run):
    return share(run, "validate.rejects", "validate.calls")
