"""sum(MII) / sum(II) over the window's bindings: the throughput of the
generated configurations against their lower bound (1.0 = every kernel
at its MII)."""

from benchkit.stats import ii_efficiency


def read(run):
    return ii_efficiency([(r.verdict.mii, r.verdict.ii) for r in run.records
                          if r.answered and r.verdict.ok])
