"""Mean number of routing PEs per binding in the window (the BandMap
paper's headline number)."""


def read(run):
    pes = [r.verdict.routing_pes for r in run.records
           if r.answered and r.verdict.ok]
    return sum(pes) / len(pes) if pes else None
