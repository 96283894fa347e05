"""PolyBench/C 4.2.1 loop kernels, hand-lowered to DFGs.

Eight kernels of the public PolyBench/C 4.2.1 suite (L.-N. Pouchet),
written out by hand from their C statements; the loop unrolled is the
innermost one:

=============  =========================================================
kernel         statement (unrolled loop)
=============  =========================================================
``jacobi-1d``  ``B[i] = 0.33333 * (A[i-1] + A[i] + A[i+1]);`` (i)
``jacobi-2d``  ``B[i][j] = 0.2 * (A[i][j] + A[i][j-1] + A[i][1+j]
               + A[1+i][j] + A[i-1][j]);`` (j)
``seidel-2d``  ``A[i][j] = (A[i-1][j-1] + A[i-1][j] + A[i-1][j+1]
               + A[i][j-1] + A[i][j] + A[i][j+1] + A[i+1][j-1]
               + A[i+1][j] + A[i+1][j+1]) / 9.0;`` (j)
``fdtd-2d``    ``hz[i][j] = hz[i][j] - 0.7 * (ex[i][j+1] - ex[i][j]
               + ey[i+1][j] - ey[i][j]);`` (j)
``gemm``       ``C[i][j] += alpha * A[i][k] * B[k][j];`` (j)
``gesummv``    ``tmp[i] = A[i][j] * x[j] + tmp[i];
               y[i] = B[i][j] * x[j] + y[i];`` (j)
``atax``       ``y[j] = y[j] + A[i][j] * tmp[i];`` (j, second loop)
``bicg``       ``s[j] = s[j] + r[i] * A[i][j];
               q[i] = q[i] + A[i][j] * p[j];`` (j)
=============  =========================================================

Lowering rules:

- The DFG is one body: the innermost loop unrolled ``unroll`` times
  over consecutive iterations.
- Each distinct array element read in the body is one VIN; repeated
  reads are merged (the spatial reuse BandMap allocates bandwidth
  for).  A loop-invariant element is one VIN per body.
- Each element written is one VOUT.  A value read after it was written
  in the same body is forwarded from its producer.
- A value written in one body and read by the next is an edge of
  distance 1.  A register-promoted scalar accumulator (``tmp[i]``,
  ``y[i]``, ``q[i]``) is an edge of distance 1 from the last update of
  the body to the first, and has no VIN or VOUT.
- Literals and scalar parameters (``alpha``, ``0.2``, ``9.0``) are the
  consuming op's resident constant, not ops.
- C's evaluation order is kept, with no reassociation; common
  subexpressions are merged within a body; every latency is 1, as in
  the paper's PE model.

`lower` returns the DFG with what each op computes: every compute op's
operator (``add``, ``sub``, ``mul``, ``div``: the first operand op the
second), its constant (the last operand where present), its operands
in C order, the element each VIN loads and each VOUT stores, and the
variable each loop-carried producer holds.  An element is
``(array, ((loop variable, offset), ...))``, one pair per subscript,
with the unrolled variable's offset taken from the body's first
iteration.
"""

from __future__ import annotations

import dataclasses

from .dfg import DFG, OpKind

KERNELS = ("jacobi-1d", "jacobi-2d", "seidel-2d", "fdtd-2d", "gemm",
           "gesummv", "atax", "bicg")

#: ``alpha`` of ``gemm``, PolyBench's ``init_array`` value, as the
#: constant of its multiplier.
GEMM_ALPHA = 1.5


@dataclasses.dataclass(frozen=True)
class Lowered:
    unroll: int
    loop: str                    # the unrolled loop variable
    dfg: DFG
    compute: dict                # op -> (operator, constant or None)
    operands: dict               # op -> operand op ids, in C order
    loads: dict                  # VIN -> element
    stores: dict                 # VOUT -> element
    carried: dict                # producer of a distance-1 edge -> var


class _Body:
    """Builds one unrolled body: ops in order of first use, then every
    consumer's operand edges in that order."""

    def __init__(self, loop: str, unroll: int) -> None:
        self.loop, self.unroll = loop, unroll
        self.order: list[tuple] = []       # (kind, name) per op id
        self.args: dict[int, list] = {}    # consumer -> operand handles
        self.compute: dict = {}
        self.loads: dict = {}
        self.stores: dict = {}
        self.vin: dict = {}                # element -> VIN
        self.value: dict = {}              # element -> op holding it
        self.cse: dict = {}
        self.lane = 0

    def _add(self, kind: OpKind, name: str) -> int:
        self.order.append((kind, name))
        return len(self.order) - 1

    def at(self, array: str, *index) -> tuple:
        """The element ``array[index]`` of the current lane; an index
        is ``(variable, offset)``, the unrolled variable shifted by the
        lane."""
        return (array, tuple((v, off + (self.lane if v == self.loop
                                         else 0)) for v, off in index))

    def load(self, elem: tuple) -> int:
        if elem in self.value:
            return self.value[elem]
        if elem not in self.vin:
            self.vin[elem] = self._add(OpKind.VIN, _name(elem))
            self.loads[self.vin[elem]] = elem
        return self.vin[elem]

    def recur(self, elem: tuple):
        """A value the previous body wrote: forwarded when this body
        has already written it, else carried over a distance-1 edge."""
        return self.value.get(elem, ("carry", elem))

    def op(self, operator: str, a, b=None, constant: float | None = None):
        key = (operator, a, b, constant)
        if key not in self.cse:
            oid = self._add(OpKind.COMPUTE, operator)
            self.args[oid] = [x for x in (a, b) if x is not None]
            self.compute[oid] = (operator, constant)
            self.cse[key] = oid
        return self.cse[key]

    def store(self, elem: tuple, value: int) -> None:
        vo = self._add(OpKind.VOUT, _name(elem))
        self.args[vo] = [value]
        self.stores[vo] = elem
        self.value[elem] = value

    def assign(self, elem: tuple, value: int) -> None:
        self.value[elem] = value

    def finish(self) -> Lowered:
        d = DFG()
        for kind, name in self.order:
            d.add_op(kind, name)
        carried: dict = {}
        operands: dict = {}
        for oid in range(len(self.order)):
            if oid not in self.args:
                continue
            ids = []
            for a in self.args[oid]:
                dist = 0
                if isinstance(a, tuple):
                    # Written by the previous body: the same element one
                    # body later in this body's terms.
                    _, (array, index) = a
                    later = (array, tuple(
                        (v, off + (self.unroll if v == self.loop else 0))
                        for v, off in index))
                    a, dist = self.value[later], 1
                    carried[a] = later
                d.add_edge(a, oid, distance=dist)
                ids.append(a)
            if oid in self.compute:
                operands[oid] = tuple(ids)
        return Lowered(self.unroll, self.loop, d, self.compute,
                       operands, self.loads, self.stores, carried)


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
    t = b.op("mul", b.load(b.at("A", ("i", 0), ("k", 0))),
             constant=GEMM_ALPHA)
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


def lower(kernel: str, unroll: int) -> Lowered:
    """The body of ``kernel`` unrolled ``unroll`` times, with what each
    op computes (module docstring)."""
    if kernel not in _STATEMENTS or unroll < 1:
        raise ValueError(f"no kernel {kernel!r} at unroll {unroll}")
    loop, statement = _STATEMENTS[kernel]
    b = _Body(loop, unroll)
    for lane in range(unroll):
        b.lane = lane
        statement(b)
    return b.finish()


def build(kernel: str, unroll: int) -> DFG:
    """The DFG of ``kernel``'s body unrolled ``unroll`` times."""
    return lower(kernel, unroll).dfg
