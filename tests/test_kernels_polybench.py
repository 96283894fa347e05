"""The hand-lowered PolyBench/C kernels: the lowering against the C
statements, and every kernel through the mapper.

The plain reference of the lowering is `interpret`, which evaluates a
lowered DFG with its operators over several bodies, carrying the
distance-1 values, and is compared with a straightforward numpy loop of
each C statement on seeded random float64 arrays.  The lowering keeps
C's order of operations, so the two agree exactly: any difference is a
lowering error, not rounding.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

from repro.core import map_dfg
from repro.core.cgra import CGRAConfig
from repro.core.dfg import OpKind
from repro.core.kernels_polybench import GEMM_ALPHA, KERNELS, build, lower
from repro.core.validate import validate_mapping

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from benchkit import dfggen, program, reference  # noqa: E402

CGRA = CGRAConfig()
ENV = {"i": 2, "k": 3}          # the loop indices held fixed
START = 1                       # first iteration of the unrolled loop
BODIES = 3
_OPS = {"add": np.add, "sub": np.subtract, "mul": np.multiply,
        "div": np.divide}


def arrays(kernel: str, seed: int) -> dict:
    """Seeded random float64 arrays; jacobi-1d's A and B are vectors."""
    rng = np.random.default_rng(seed)
    rows = () if kernel == "jacobi-1d" else (8,)
    out = {a: rng.standard_normal(rows + (24,)) for a in ("A", "B")}
    out.update({a: rng.standard_normal((8, 24))
                for a in ("C", "ex", "ey", "hz")})
    out.update({a: rng.standard_normal(24)
                for a in ("x", "y", "tmp", "s", "q", "p", "r")})
    return out


def interpret(low, arrs: dict, n_bodies: int) -> dict:
    """Run ``n_bodies`` consecutive bodies of a lowered kernel from
    iteration ``START`` on copies of ``arrs``."""
    arrs = {k: v.copy() for k, v in arrs.items()}
    d, u = low.dfg, low.unroll
    dist = {(e.src, e.dst): e.distance for e in d.edges}

    def at(elem, base):
        array, index = elem
        return array, tuple((base if v == low.loop else ENV[v]) + off
                            for v, off in index)

    def get(elem, base):
        array, idx = at(elem, base)
        return arrs[array][idx]

    carry = {p: get(var, START - u) for p, var in low.carried.items()}
    order = d.topo_order()
    base = START
    for b in range(n_bodies):
        base = START + b * u
        val = {v: get(elem, base) for v, elem in low.loads.items()}
        writes = []
        for oid in order:
            kind = d.ops[oid].kind
            if kind == OpKind.COMPUTE:
                operator, constant = low.compute[oid]
                args = [carry[a] if dist[(a, oid)] else val[a]
                        for a in low.operands[oid]]
                if constant is not None:
                    args.append(constant)
                val[oid] = _OPS[operator](*args)
            elif kind == OpKind.VOUT:
                (src,) = d.predecessors(oid)
                writes.append((at(low.stores[oid], base), val[src]))
        carry = {p: val[p] for p in low.carried}
        for (array, idx), v in writes:
            arrs[array][idx] = v
    for p, var in low.carried.items():
        array, idx = at(var, base)
        arrs[array][idx] = carry[p]
    return arrs


def c_loop(kernel: str, arrs: dict, n: int) -> dict:
    """The C statement of ``kernel`` over ``n`` iterations from
    ``START``, as a plain loop."""
    a = {k: v.copy() for k, v in arrs.items()}
    i, k = ENV["i"], ENV["k"]
    for j in range(START, START + n):
        if kernel == "jacobi-1d":
            A, B = a["A"], a["B"]
            B[j] = 0.33333 * (A[j - 1] + A[j] + A[j + 1])
        elif kernel == "jacobi-2d":
            A, B = a["A"], a["B"]
            B[i, j] = 0.2 * (A[i, j] + A[i, j - 1] + A[i, 1 + j]
                             + A[1 + i, j] + A[i - 1, j])
        elif kernel == "seidel-2d":
            A = a["A"]
            A[i, j] = (A[i - 1, j - 1] + A[i - 1, j] + A[i - 1, j + 1]
                       + A[i, j - 1] + A[i, j] + A[i, j + 1]
                       + A[i + 1, j - 1] + A[i + 1, j]
                       + A[i + 1, j + 1]) / 9.0
        elif kernel == "fdtd-2d":
            ex, ey, hz = a["ex"], a["ey"], a["hz"]
            hz[i, j] = hz[i, j] - 0.7 * (ex[i, j + 1] - ex[i, j]
                                         + ey[i + 1, j] - ey[i, j])
        elif kernel == "gemm":
            a["C"][i, j] += GEMM_ALPHA * a["A"][i, k] * a["B"][k, j]
        elif kernel == "gesummv":
            A, B, x = a["A"], a["B"], a["x"]
            a["tmp"][i] = A[i, j] * x[j] + a["tmp"][i]
            a["y"][i] = B[i, j] * x[j] + a["y"][i]
        elif kernel == "atax":
            a["y"][j] = a["y"][j] + a["A"][i, j] * a["tmp"][i]
        elif kernel == "bicg":
            A = a["A"]
            a["s"][j] = a["s"][j] + a["r"][i] * A[i, j]
            a["q"][i] = a["q"][i] + A[i, j] * a["p"][j]
    return a


# ------------------------------------------------------- the lowering
@pytest.mark.parametrize("unroll", [1, 2, 4])
@pytest.mark.parametrize("kernel", KERNELS)
def test_lowering_equals_the_c_loop_exactly(kernel, unroll):
    low = lower(kernel, unroll)
    for seed in (0, 1, 2 ** 31 + 5):
        data = arrays(kernel, seed)
        got = interpret(low, data, BODIES)
        want = c_loop(kernel, data, BODIES * unroll)
        for name, arr in want.items():
            assert np.array_equal(got[name], arr), (kernel, unroll, name)
        assert any(not np.array_equal(want[n], data[n]) for n in data)


def test_lowering_rules_on_jacobi_1d():
    """Shared reads merge into one VIN, each lane writes one VOUT, and
    the constant rides on the multiplier."""
    low = lower("jacobi-1d", 2)
    d = low.dfg
    names = sorted(d.ops[v].name for v in d.v_i)
    assert names == ["A[i+1]", "A[i+2]", "A[i-1]", "A[i]"]
    assert sorted(d.ops[v].name for v in d.v_o) == ["B[i+1]", "B[i]"]
    muls = [op for op, (kind, c) in low.compute.items() if kind == "mul"]
    assert [low.compute[m][1] for m in muls] == [0.33333, 0.33333]
    assert all(e.distance == 0 for e in d.edges)


@pytest.mark.parametrize("kernel,unroll,rec", [
    ("seidel-2d", 1, 7), ("seidel-2d", 4, 28), ("gesummv", 4, 4),
    ("bicg", 2, 2), ("gemm", 4, 1)])
def test_recurrences(kernel, unroll, rec):
    """Carried values close a recurrence over the whole body: seidel's
    A[i][j-1] through seven ops a lane, the accumulators through one."""
    assert build(kernel, unroll).rec_mii() == rec


def test_unknown_kernel():
    with pytest.raises(ValueError):
        build("lu", 2)
    with pytest.raises(ValueError):
        build("gemm", 0)


# ----------------------------------------------------- on the mapper
def as_graph(d) -> dfggen.Graph:
    g = dfggen.Graph()
    for oid, op in d.ops.items():
        g.ops[oid] = dfggen.Op(op.kind.value, op.name, op.latency,
                               op.clone_of)
    g.edges = [(e.src, e.dst, e.distance) for e in d.edges]
    g.next_id = d._next_id
    return g


@pytest.mark.parametrize("mode", ["bandmap", "busmap"])
@pytest.mark.parametrize("unroll", [1, 2, 4])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_maps_and_replays(kernel, unroll, mode):
    """Every kernel binds on the paper's 4x4 fabric, passes the
    validator and the benchmark's plain reference, and carries no
    infeasibility claim."""
    d = build(kernel, unroll)
    res = map_dfg(d, CGRA, mode=mode)
    assert res.ok and not res.proved_infeasible
    assert validate_mapping(res.sched, CGRA, res.placement).ok
    fab = reference.Fabric()
    assert reference.check_verdict(as_graph(d), fab, 32,
                                   program.verdict(res)) == []
