"""The traffic generator: turns a mix file and a seed into requests.

A mix is a JSON file under ``bench/traffic/``.  Its ``driver`` names the
module ``bench/drivers/<driver>.py`` that sends the requests and times
them (see `harness.load_driver`); the rest of the file is the driver's
parameters.  The drivers of this directory read:

``cycle``
    the requests of one cycle, each ``{"name", "family", "params"}``
    plus an optional ``"mode"`` (``bandmap`` | ``busmap``, default the
    configuration's) and ``"expect"`` (``"binding"``: the kernel is
    known to bind, so a proof that it cannot is wrong; default
    ``"any"``);
``order``
    ``"fixed"`` (the cycle as listed, every cycle) or ``"shuffle"``
    (each cycle in a fresh order drawn from the seed).

Every seed gets the same requests; the seed only orders them, so two
seeds ask for the same work.  (Relabelling each request's op ids from
the seed, as a client resubmitting a kernel would, changes how long the
mapper searches, by minutes on one kernel, so no mix does it; PERF.md
lists that under its open questions.)
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator

import numpy as np

from . import dfggen


@dataclasses.dataclass
class Request:
    index: int
    name: str
    graph: dfggen.Graph
    mode: str
    expect: str


def check_cycle(mix: dict) -> None:
    """Refuse a cycle mix this generator cannot honour."""
    if mix.get("order") not in ("fixed", "shuffle"):
        raise ValueError(f"unknown order {mix.get('order')!r}")
    if not mix.get("cycle"):
        raise ValueError("a cycle mix needs at least one request")
    for item in mix["cycle"]:
        if item.get("expect", "any") not in ("any", "binding"):
            raise ValueError(f"unknown expect in {item}")
        dfggen.build(item["family"], item["params"])


def _request(index: int, item: dict, default_mode: str) -> Request:
    return Request(index, item["name"],
                   dfggen.build(item["family"], item["params"]),
                   item.get("mode", default_mode),
                   item.get("expect", "any"))


def distinct(mix: dict, default_mode: str) -> list[Request]:
    """One request per entry of the cycle."""
    return [_request(-1, item, default_mode) for item in mix["cycle"]]


def requests(mix: dict, seed: int, default_mode: str) -> Iterator[Request]:
    """The endless request stream of a cycle mix under ``seed``."""
    check_cycle(mix)
    cycle = mix["cycle"]
    shuffle = np.random.default_rng([seed % 2 ** 64, 0])
    index = itertools.count()
    while True:
        order = list(range(len(cycle)))
        if mix["order"] == "shuffle":
            shuffle.shuffle(order)
        for k in order:
            yield _request(next(index), cycle[k], default_mode)
