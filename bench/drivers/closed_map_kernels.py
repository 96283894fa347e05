"""One client in a closed loop through `map_dfg` over named PolyBench/C
kernels: the next request is sent when the previous verdict is back, in
whole cycles of the mix, and no cycle starts after the window's nominal
length (as `closed_map_dfg`).

A mix for this driver has ``order`` (``"fixed"`` | ``"shuffle"``) and a
``cycle`` of requests ``{"name", "kernel", "unroll"}`` plus an optional
``"mode"`` (``bandmap`` | ``busmap``, default the configuration's) and
``"expect"`` (``"binding"`` | ``"any"``, default ``"any"``).  Every
graph is built from the benchmark's frozen copy, `benchkit.polybench`;
the seed only orders each cycle.

Before the window the driver maps ``c[i] = a[i] + b[i]`` once, and
refuses to run (an exception, so the run exits non-zero) when the
program does not bind it: every kernel of the mix but gemm has an op
that reads two memory operands, and a program that cannot bind one
answers the mix with wrong verdicts."""

import itertools
import time

import numpy as np

from benchkit import dfggen, harness, polybench, program, traffic


def check(mix):
    """Refuse a mix this driver cannot honour."""
    if mix.get("order") not in ("fixed", "shuffle"):
        raise ValueError(f"unknown order {mix.get('order')!r}")
    if not mix.get("cycle"):
        raise ValueError("a cycle mix needs at least one request")
    for item in mix["cycle"]:
        if not isinstance(item.get("name"), str):
            raise ValueError(f"request without a name: {item}")
        if item.get("expect", "any") not in ("any", "binding"):
            raise ValueError(f"unknown expect in {item}")
        if item.get("mode", "bandmap") not in ("bandmap", "busmap"):
            raise ValueError(f"unknown mode in {item}")
        polybench.build(item.get("kernel"), item.get("unroll"))


def _request(index, item, default_mode):
    return traffic.Request(index, item["name"],
                           polybench.build(item["kernel"], item["unroll"]),
                           item.get("mode", default_mode),
                           item.get("expect", "any"))


def distinct(mix, default_mode):
    return [_request(-1, item, default_mode) for item in mix["cycle"]]


def requests(mix, seed, default_mode):
    """The endless request stream of the mix under ``seed``."""
    check(mix)
    cycle = mix["cycle"]
    shuffle = np.random.default_rng([seed % 2 ** 64, 0])
    index = itertools.count()
    while True:
        order = list(range(len(cycle)))
        if mix["order"] == "shuffle":
            shuffle.shuffle(order)
        for k in order:
            yield _request(next(index), cycle[k], default_mode)


def vector_add():
    """``c[i] = a[i] + b[i]``: one op reading two memory operands."""
    g = dfggen.Graph()
    a, b = g.add_op(dfggen.VIN, "a[i]"), g.add_op(dfggen.VIN, "b[i]")
    add = g.add_op(dfggen.COMPUTE, "add")
    c = g.add_op(dfggen.VOUT, "c[i]")
    for src, dst in ((a, add), (b, add), (add, c)):
        g.add_edge(src, dst)
    return g


def require_two_operand_binding(cgra, options):
    res = program.map_request(vector_add(), cgra,
                              {**options, "engine": "numpy", "max_ii": 4})
    if not res.ok:
        raise RuntimeError(
            "the program binds no op that reads two memory operands "
            "(c[i] = a[i] + b[i] at II <= 4), which every kernel of this "
            "mix but gemm needs")


def window(mix, seed, seconds, cgra, options, traced):
    require_two_operand_binding(cgra, options)
    stream = requests(mix, seed, options.get("mode", "bandmap"))
    cycle = len(mix["cycle"])
    records = []
    t0 = time.perf_counter()
    for req in stream:
        if req.index % cycle == 0 and time.perf_counter() - t0 >= seconds:
            break
        records.append(harness.send(req, cgra, options, traced))
    return t0, records
