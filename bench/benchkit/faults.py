"""Faults planted in the timed path, to show that the check catches them.

Each fault is a context manager that patches the program in this
process only; the benchmark's own runs install none.

- ``validator_off``: the control.  The program's final replay of a
  candidate binding (`core.validate.validate_mapping`) still runs but
  its rejection is ignored, so a placement that breaks the fabric's bus
  or register capacity is returned as a binding: the step a later
  change could be tempted to drop, since every candidate pays for it.
- ``answer_altered``: every binding comes back with one compute op moved
  to another PE, as if the answer were corrupted where it is produced.
- ``no_verdict``: every request comes back as a search that gave up,
  with neither a binding nor a proof.
"""

from __future__ import annotations

import contextlib
import dataclasses


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def validator_off():
    from repro.core import bandmap
    real = bandmap.validate_mapping

    def accept(sched, cgra, placement):
        rep = real(sched, cgra, placement)
        return dataclasses.replace(rep, ok=True)

    with _patch(bandmap, "validate_mapping", accept):
        yield


def _wrap_map(alter):
    from repro.core import bandmap
    real = bandmap.map_dfg

    def mapped(*args, **kwargs):
        return alter(real(*args, **kwargs), args[1])

    return _patch(bandmap, "map_dfg", mapped)


def _move_one(res, cgra):
    if not res.ok:
        return res
    quads = sorted(op for op, v in res.placement.items() if v.kind == "quad"
                   and v.drive is None)
    if not quads:
        return res
    op = quads[0]
    v = res.placement[op]
    pe = ((v.pe[0] + 1) % cgra.rows, (v.pe[1] + 1) % cgra.cols)
    placement = dict(res.placement)
    placement[op] = dataclasses.replace(v, pe=pe)
    return dataclasses.replace(res, placement=placement)


def _give_up(res, cgra):
    return dataclasses.replace(res, ok=False, proved_infeasible=False,
                               certificates=[])


@contextlib.contextmanager
def answer_altered():
    with _wrap_map(_move_one):
        yield


@contextlib.contextmanager
def no_verdict():
    with _wrap_map(_give_up):
        yield


FAULTS = {"validator_off": validator_off, "answer_altered": answer_altered,
          "no_verdict": no_verdict}
