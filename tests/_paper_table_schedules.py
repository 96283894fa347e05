"""Digests of every schedule the benchmark's ``paper_table`` requests
reach, for the scheduler guard in ``test_schedule_stagger.py``.

One digest per (request, II): the (time, delivery, ports, ops, edges)
of the schedules at jitters 0-3, in that order ("none" where the
scheduler emits nothing), at every II from MII to ``max_ii`` 32 with
every other option at its default, exactly the (II, jitter) range the
benchmark's device warm-up walks.  Refresh the pinned copy with

    PYTHONPATH=src python tests/_paper_table_schedules.py

only on purpose: it is the record that schedules with at most one
input operand per op do not move.
"""

from __future__ import annotations

import hashlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MIX = os.path.join(ROOT, "bench", "traffic", "paper_table.json")
PINNED = os.path.join(HERE, "data", "paper_table_schedules.json")
MAX_II = 32


def requests():
    """(name, DFG, mode) for every request of the mix, built with the
    program's own generators (the benchmark's frozen copies build the
    same graphs: bench/tests/test_bench_dfggen.py)."""
    from repro.core.kernels_cnkm import make_cnkm
    from repro.core.workloads import make_loop_kernel
    with open(MIX) as f:
        cycle = json.load(f)["cycle"]
    out = []
    for item in cycle:
        p = item["params"]
        d = make_cnkm(**p) if item["family"] == "cnkm" \
            else make_loop_kernel(**p)
        out.append((item["name"], d, item.get("mode", "bandmap")))
    return out


def schedule_form(s) -> list:
    d = s.dfg
    return [s.ii, sorted(s.time.items()), sorted(s.delivery.items()),
            sorted(s.ports_allocated.items()),
            [(i, o.kind.value, o.name, o.clone_of)
             for i, o in sorted(d.ops.items())],
            [(e.src, e.dst, e.distance) for e in d.edges]]


def digests(dfg, mode: str, cgra, each=None) -> dict[str, str]:
    """II -> digest of the schedules at jitters 0-3; ``each`` is called
    with every schedule emitted."""
    from repro.core.schedule import mii, schedule_dfg
    out = {}
    for ii in range(mii(dfg, cgra), MAX_II + 1):
        forms = []
        for jitter in range(4):
            try:
                s = schedule_dfg(dfg, cgra, mode=mode, ii=ii, max_ii=ii,
                                 jitter=jitter)
            except RuntimeError:
                forms.append("none")
                continue
            if each is not None:
                each(s)
            forms.append(schedule_form(s))
        blob = json.dumps(forms, sort_keys=True).encode()
        out[str(ii)] = hashlib.sha256(blob).hexdigest()[:20]
    return out


def main() -> None:
    from repro.core.cgra import CGRAConfig
    cgra = CGRAConfig()
    pinned = {name: digests(d, mode, cgra)
              for name, d, mode in requests()}
    with open(PINNED, "w") as f:
        json.dump(pinned, f, indent=0, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    main()
