"""Frozen copy of the hand-lowered PolyBench/C 4.2.1 kernels, structure
only.

The benchmark builds every ``polybench_table`` request from this copy,
never from the program's `repro.core.kernels_polybench`, so a later
change to the program's lowering cannot move the yardstick.  It makes
the same ops (kinds, names), edges and distances in the same order;
`bench/tests/test_bench_polybench.py` checks that both build the same
graph at every (kernel, unroll) of the mix.  What each op computes is
left out: the benchmark checks bindings, not values.

The lowering rules (one body is the innermost loop unrolled ``unroll``
times; each distinct element read is one VIN and each element written
one VOUT; a value read after it was written in the body is forwarded;
a value the previous body wrote, or a register-promoted accumulator,
is an edge of distance 1; literals and scalar parameters are constants
of their op; C's order of operations; latency 1) are the program
module's.
"""

from __future__ import annotations

from .dfggen import COMPUTE, VIN, VOUT, Graph

KERNELS = ("jacobi-1d", "jacobi-2d", "seidel-2d", "fdtd-2d", "gemm",
           "gesummv", "atax", "bicg")


class _Body:
    def __init__(self, loop: str, unroll: int) -> None:
        self.loop, self.unroll = loop, unroll
        self.order: list[tuple] = []
        self.args: dict[int, list] = {}
        self.vin: dict = {}
        self.value: dict = {}
        self.cse: dict = {}
        self.lane = 0

    def _add(self, kind: str, name: str) -> int:
        self.order.append((kind, name))
        return len(self.order) - 1

    def at(self, array: str, *index) -> tuple:
        return (array, tuple((v, off + (self.lane if v == self.loop
                                         else 0)) for v, off in index))

    def load(self, elem: tuple) -> int:
        if elem in self.value:
            return self.value[elem]
        if elem not in self.vin:
            self.vin[elem] = self._add(VIN, _name(elem))
        return self.vin[elem]

    def recur(self, elem: tuple):
        return self.value.get(elem, ("carry", elem))

    def op(self, operator: str, a, b=None, constant: float | None = None):
        key = (operator, a, b, constant)
        if key not in self.cse:
            oid = self._add(COMPUTE, operator)
            self.args[oid] = [x for x in (a, b) if x is not None]
            self.cse[key] = oid
        return self.cse[key]

    def store(self, elem: tuple, value: int) -> None:
        vo = self._add(VOUT, _name(elem))
        self.args[vo] = [value]
        self.value[elem] = value

    def assign(self, elem: tuple, value: int) -> None:
        self.value[elem] = value

    def finish(self) -> Graph:
        g = Graph()
        for kind, name in self.order:
            g.add_op(kind, name)
        for oid in range(len(self.order)):
            for a in self.args.get(oid, ()):
                dist = 0
                if isinstance(a, tuple):
                    _, (array, index) = a
                    later = (array, tuple(
                        (v, off + (self.unroll if v == self.loop else 0))
                        for v, off in index))
                    a, dist = self.value[later], 1
                g.add_edge(a, oid, dist)
        return g


def _name(elem: tuple) -> str:
    array, index = elem
    return array + "".join(
        f"[{v}{off:+d}]" if off else f"[{v}]" for v, off in index)


def _jacobi_1d(b: _Body) -> None:
    t = b.op("add", b.load(b.at("A", ("i", -1))), b.load(b.at("A", ("i", 0))))
    t = b.op("add", t, b.load(b.at("A", ("i", 1))))
    b.store(b.at("B", ("i", 0)), b.op("mul", t, constant=0.33333))


def _jacobi_2d(b: _Body) -> None:
    t = b.op("add", b.load(b.at("A", ("i", 0), ("j", 0))),
             b.load(b.at("A", ("i", 0), ("j", -1))))
    for di, dj in ((0, 1), (1, 0), (-1, 0)):
        t = b.op("add", t, b.load(b.at("A", ("i", di), ("j", dj))))
    b.store(b.at("B", ("i", 0), ("j", 0)), b.op("mul", t, constant=0.2))


def _seidel_2d(b: _Body) -> None:
    t = b.op("add", b.load(b.at("A", ("i", -1), ("j", -1))),
             b.load(b.at("A", ("i", -1), ("j", 0))))
    t = b.op("add", t, b.load(b.at("A", ("i", -1), ("j", 1))))
    t = b.op("add", t, b.recur(b.at("A", ("i", 0), ("j", -1))))
    for di, dj in ((0, 0), (0, 1), (1, -1), (1, 0), (1, 1)):
        t = b.op("add", t, b.load(b.at("A", ("i", di), ("j", dj))))
    b.store(b.at("A", ("i", 0), ("j", 0)), b.op("div", t, constant=9.0))


def _fdtd_2d(b: _Body) -> None:
    t = b.op("sub", b.load(b.at("ex", ("i", 0), ("j", 1))),
             b.load(b.at("ex", ("i", 0), ("j", 0))))
    t = b.op("add", t, b.load(b.at("ey", ("i", 1), ("j", 0))))
    t = b.op("sub", t, b.load(b.at("ey", ("i", 0), ("j", 0))))
    t = b.op("mul", t, constant=0.7)
    hz = b.at("hz", ("i", 0), ("j", 0))
    b.store(hz, b.op("sub", b.load(hz), t))


def _gemm(b: _Body) -> None:
    t = b.op("mul", b.load(b.at("A", ("i", 0), ("k", 0))), constant=1.5)
    t = b.op("mul", t, b.load(b.at("B", ("k", 0), ("j", 0))))
    c = b.at("C", ("i", 0), ("j", 0))
    b.store(c, b.op("add", b.load(c), t))


def _gesummv(b: _Body) -> None:
    for mat, acc in (("A", "tmp"), ("B", "y")):
        t = b.op("mul", b.load(b.at(mat, ("i", 0), ("j", 0))),
                 b.load(b.at("x", ("j", 0))))
        var = b.at(acc, ("i", 0))
        b.assign(var, b.op("add", t, b.recur(var)))


def _atax(b: _Body) -> None:
    t = b.op("mul", b.load(b.at("A", ("i", 0), ("j", 0))),
             b.load(b.at("tmp", ("i", 0))))
    y = b.at("y", ("j", 0))
    b.store(y, b.op("add", b.load(y), t))


def _bicg(b: _Body) -> None:
    a = b.load(b.at("A", ("i", 0), ("j", 0)))
    s = b.at("s", ("j", 0))
    b.store(s, b.op("add", b.load(s),
                    b.op("mul", b.load(b.at("r", ("i", 0))), a)))
    q = b.at("q", ("i", 0))
    b.assign(q, b.op("add", b.recur(q),
                     b.op("mul", a, b.load(b.at("p", ("j", 0))))))


_STATEMENTS = {
    "jacobi-1d": ("i", _jacobi_1d), "jacobi-2d": ("j", _jacobi_2d),
    "seidel-2d": ("j", _seidel_2d), "fdtd-2d": ("j", _fdtd_2d),
    "gemm": ("j", _gemm), "gesummv": ("j", _gesummv),
    "atax": ("j", _atax), "bicg": ("j", _bicg),
}


def build(kernel: str, unroll: int) -> Graph:
    """The graph of ``kernel``'s body unrolled ``unroll`` times."""
    if kernel not in _STATEMENTS or not isinstance(unroll, int) \
            or isinstance(unroll, bool) or unroll < 1:
        raise ValueError(f"no kernel {kernel!r} at unroll {unroll!r}")
    loop, statement = _STATEMENTS[kernel]
    b = _Body(loop, unroll)
    for lane in range(unroll):
        b.lane = lane
        statement(b)
    return b.finish()
