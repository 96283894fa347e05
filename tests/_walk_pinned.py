"""Pinned behaviour of the (II, jitter) walk, for ``test_walk_pinned.py``.

For every request of the benchmark's ``paper_table`` mix (the paper's 14
CnKm (kernel, mode) pairs, then 30 generated 4x4 loop kernels), at
default options:

- the portfolio backend's traced span sequence, in start order: (name,
  parent name, ``ii``, ``jitter``, ``round``, ``best``, counts);
- the exact backend's result fields ``ok``, ``ii``, ``optimal``,
  ``proved_infeasible``, ``attempts`` and each certificate's (ii,
  jitter, stage, nodes).

Two more cases map ``loop4x4s124`` and ``loop4x4s128`` on the device
engine (interpret mode on the CPU): the benchmark's engine.  Both bind
in the first harvest round of their binding II, so neither reaches the
harvest's cap of 16 re-seeded complete seeds a round.  Refresh the
pinned copy with

    PYTHONPATH=src python tests/_walk_pinned.py

only on purpose: it is the record that restructuring the walk moved no
seed, span or counter.
"""

from __future__ import annotations

import json
import os

from _paper_table_schedules import requests

HERE = os.path.dirname(os.path.abspath(__file__))
PINNED = os.path.join(HERE, "data", "walk_pinned.json")
DEVICE_CASES = ("loop4x4s124", "loop4x4s128")


def cases() -> dict:
    """Case name -> (DFG, mode, engine)."""
    out = {name: (d, mode, "numpy") for name, d, mode in requests()}
    for name in DEVICE_CASES:
        d, mode, _ = out[name]
        out[name + ".device"] = (d, mode, "device")
    return out


def portfolio_spans(dfg, mode: str, engine: str) -> list:
    from repro.core import map_dfg
    from repro.core.cgra import CGRAConfig
    from repro.obs.trace import Tracer
    tr = Tracer()
    map_dfg(dfg, CGRAConfig(), mode=mode, engine=engine, tracer=tr)
    recs = sorted(tr.finished, key=lambda r: r.sid)
    names = {r.sid: r.name for r in recs}
    return [[r.name, names.get(r.parent)]
            + [r.attrs.get(k) for k in ("ii", "jitter", "round", "best")]
            + [dict(sorted(r.counts.items()))] for r in recs]


def exact_fields(dfg, mode: str) -> dict:
    from repro.core import map_dfg
    from repro.core.cgra import CGRAConfig
    r = map_dfg(dfg, CGRAConfig(), mode=mode, backend="exact")
    return {"ok": r.ok, "ii": r.ii, "optimal": r.optimal,
            "proved_infeasible": r.proved_infeasible,
            "attempts": r.attempts,
            "certificates": [[c.ii, c.jitter, c.stage, c.nodes]
                             for c in r.certificates]}


def record(dfg, mode: str, engine: str) -> dict:
    out = {"portfolio": portfolio_spans(dfg, mode, engine)}
    if engine == "numpy":
        out["exact"] = exact_fields(dfg, mode)
    return out


def load() -> dict:
    with open(PINNED) as f:
        return json.load(f)


def main() -> None:
    pinned = {name: record(*case) for name, case in cases().items()}
    with open(PINNED, "w") as f:
        f.write("{\n")
        f.write(",\n".join(f"{json.dumps(k)}: {json.dumps(v)}"
                           for k, v in pinned.items()))
        f.write("\n}\n")


if __name__ == "__main__":
    main()
