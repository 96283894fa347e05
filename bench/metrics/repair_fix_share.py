"""Share, in %, of ejection-chain repair tries whose result covers every
op: ``repair.fixed`` over ``repair.tries``, both counted on the ``repair``
spans; None when no try ran."""

from benchkit.counts import share


def read(run):
    return share(run, "repair.fixed", "repair.tries")
