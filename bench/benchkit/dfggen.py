"""Frozen copies of the DFG generators the benchmark's traffic draws from.

The benchmark builds every request from these copies, never from the
program's own generators, so a later change to the program's workload
code cannot move the yardstick.  Each builder makes the same calls on
the same `numpy` random stream as the generator it was copied from, and
`bench/tests/test_bench_dfggen.py` checks that both build the same graph
(ops, kinds, edges, distances) at every parameter the cells use.

A graph here is a plain `Graph`: ops keyed by integer id in insertion
order, each ``(kind, name, latency, clone_of)``, and a list of
``(src, dst, distance)`` edges.  Kinds are ``"vin"``, ``"vout"``,
``"compute"`` and ``"route"``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

VIN, VOUT, COMPUTE, ROUTE = "vin", "vout", "compute", "route"


@dataclasses.dataclass(frozen=True)
class Op:
    kind: str
    name: str
    latency: int = 1
    clone_of: int = -1


@dataclasses.dataclass
class Graph:
    ops: dict = dataclasses.field(default_factory=dict)   # id -> Op
    edges: list = dataclasses.field(default_factory=list)  # (s, d, dist)
    next_id: int = 0

    def add_op(self, kind: str, name: str) -> int:
        oid = self.next_id
        self.next_id += 1
        self.ops[oid] = Op(kind, name)
        return oid

    def add_edge(self, src: int, dst: int, distance: int = 0) -> None:
        self.edges.append((src, dst, distance))

    def remove_edge(self, src: int, dst: int) -> None:
        self.edges = [e for e in self.edges
                      if not (e[0] == src and e[1] == dst)]


# ------------------------------------------------------------ families
def make_cnkm(n: int, m: int) -> Graph:
    """CnKm MAC lattice (BandMap paper, section IV-A): n input VIOs each
    reused by m MACs, MACs chained over the inputs per output channel,
    m output VOOs."""
    d = Graph()
    vins = [d.add_op(VIN, f"in{i}") for i in range(n)]
    mac = [[d.add_op(COMPUTE, f"mac{i}_{j}") for j in range(m)]
           for i in range(n)]
    for i in range(n):
        for j in range(m):
            d.add_edge(vins[i], mac[i][j])
            if i > 0:
                d.add_edge(mac[i - 1][j], mac[i][j])
    vouts = [d.add_op(VOUT, f"out{j}") for j in range(m)]
    for j in range(m):
        d.add_edge(mac[n - 1][j], vouts[j])
    return d


def make_loop_kernel(n_chains: int = 4, chain_len: int = 4,
                     n_inputs: int = 3, n_outputs: int = 2, *,
                     n_carries: int = 1, max_distance: int = 2,
                     cross_links: int = 1, vin_carry_distance: int = 0,
                     seed: int = 0) -> Graph:
    """Chain-structured loop kernel with loop-carried accumulators:
    ``n_chains`` chains of ``chain_len`` compute ops, one shared VIO per
    level, ``n_carries`` back edges of distance 1..``max_distance``,
    ``cross_links`` links between adjacent chains, one VOO per chain
    end up to ``n_outputs``."""
    assert n_carries <= n_chains
    rng = np.random.default_rng(seed)
    d = Graph()
    n_inputs = min(n_inputs, chain_len)
    vins = [d.add_op(VIN, f"in{i}") for i in range(n_inputs)]
    levels = list(range(n_inputs)) + [
        int(rng.integers(0, n_inputs))
        for _ in range(chain_len - n_inputs)]
    rng.shuffle(levels)
    chains = [[d.add_op(COMPUTE, f"c{j}_{lv}")
               for lv in range(chain_len)] for j in range(n_chains)]
    for lv in range(chain_len):
        if lv < len(levels):
            for j in range(n_chains):
                d.add_edge(vins[levels[lv]], chains[j][lv])
    for j in range(n_chains):
        for a, b in zip(chains[j], chains[j][1:]):
            d.add_edge(a, b)
    for j in range(n_carries):
        dist = int(rng.integers(1, max_distance + 1))
        d.add_edge(chains[j][-1], chains[j][0], distance=dist)
    for _ in range(cross_links):
        if n_chains < 2:
            break
        j = int(rng.integers(0, n_chains - 1))
        lv = int(rng.integers(0, chain_len - 1))
        d.add_edge(chains[j][lv], chains[j + 1][lv + 1])
    if vin_carry_distance > 0:
        vin = vins[levels[-1]] if levels else vins[-1]
        late = chains[-1][len(levels) - 1 if levels else -1]
        d.remove_edge(vin, late)
        d.add_edge(vin, late, distance=vin_carry_distance)
    for j in range(min(n_outputs, n_chains)):
        vo = d.add_op(VOUT, f"out{j}")
        d.add_edge(chains[j][-1], vo)
    return d


def make_stencil(points: int = 4, taps: int = 3, *, seed: int = 0) -> Graph:
    """1-D ``taps``-point stencil over ``points`` outputs (``seed`` is
    accepted for uniformity; the shape is fixed)."""
    del seed
    d = Graph()
    vins = [d.add_op(VIN, f"in{i}") for i in range(points + taps - 1)]
    for j in range(points):
        prev = None
        for k in range(taps):
            mac = d.add_op(COMPUTE, f"mac{j}_{k}")
            d.add_edge(vins[j + k], mac)
            if prev is not None:
                d.add_edge(prev, mac)
            prev = mac
        vo = d.add_op(VOUT, f"out{j}")
        d.add_edge(prev, vo)
    return d


def make_reduction(width: int = 8, arity: int = 2, *,
                   seed: int = 0) -> Graph:
    """``width`` inputs, one leaf op each, then an ``arity``-ary tree to
    one output."""
    del seed
    assert arity >= 2
    d = Graph()
    frontier = []
    for i in range(width):
        vin = d.add_op(VIN, f"in{i}")
        leaf = d.add_op(COMPUTE, f"leaf{i}")
        d.add_edge(vin, leaf)
        frontier.append(leaf)
    level = 0
    while len(frontier) > 1:
        nxt = []
        for g in range(0, len(frontier), arity):
            group = frontier[g:g + arity]
            if len(group) == 1:
                nxt.extend(group)
                continue
            red = d.add_op(COMPUTE, f"r{level}_{g // arity}")
            for s in group:
                d.add_edge(s, red)
            nxt.append(red)
        frontier = nxt
        level += 1
    vo = d.add_op(VOUT, "out0")
    d.add_edge(frontier[0], vo)
    return d


FAMILIES = {
    "cnkm": make_cnkm,
    "loop": make_loop_kernel,
    "stencil": make_stencil,
    "reduction": make_reduction,
}


def build(family: str, params: dict) -> Graph:
    """One kernel from a family name and its parameters."""
    if family not in FAMILIES:
        raise KeyError(f"unknown kernel family {family!r}; "
                       f"have {sorted(FAMILIES)}")
    return FAMILIES[family](**params)


def serve_catalog(scale: str = "8x8", *, seed: int = 0) -> list:
    """The served population's distinct kernels as ``(name, family,
    params)``: six CnKm kernels, two stencils, two reductions and three
    loop kernels sized for the ``scale`` fabric."""
    mult = {"4x4": 1, "8x8": 2, "16x16": 4}[scale]
    specs = [
        ("c2k4", "cnkm", dict(n=2, m=4)),
        ("c2k6", "cnkm", dict(n=2, m=6)),
        ("c3k6", "cnkm", dict(n=3, m=6)),
        ("c4k4", "cnkm", dict(n=4, m=4)),
        ("c4k8", "cnkm", dict(n=4, m=8)),
        ("c5k5", "cnkm", dict(n=5, m=5)),
        ("stencil4", "stencil", dict(points=4, taps=3)),
        (f"stencil{3 * mult}", "stencil", dict(points=3 * mult, taps=3)),
        (f"reduce{8 * mult}", "reduction", dict(width=8 * mult, arity=2)),
        ("reduce6a3", "reduction", dict(width=6, arity=3)),
    ]
    for k in range(3):
        specs.append((f"loop{mult}x{k}", "loop",
                      dict(n_chains=2 * mult, chain_len=4,
                           n_inputs=min(2 + mult, 4), n_outputs=2,
                           n_carries=min(k, 2 * mult), max_distance=2,
                           seed=seed + k)))
    return specs


# ----------------------------------------------------------- relabelling
def permute(d: Graph, *, seed: int = 0) -> Graph:
    """The same kernel under a random op-id relabelling, with the op and
    edge iteration orders shuffled too."""
    rng = np.random.default_rng(seed)
    ids = sorted(d.ops)
    shuffled = [ids[i] for i in rng.permutation(len(ids))]
    mapping = dict(zip(ids, shuffled))
    out = Graph()
    for oid in [ids[i] for i in rng.permutation(len(ids))]:
        op = d.ops[oid]
        out.ops[mapping[oid]] = dataclasses.replace(
            op, clone_of=mapping[op.clone_of] if op.clone_of >= 0 else -1)
    edges = [(mapping[s], mapping[t], dist) for s, t, dist in d.edges]
    out.edges = [edges[i] for i in rng.permutation(len(edges))]
    out.next_id = max(out.ops, default=-1) + 1
    return out


def zipf_draws(n_items: int, n_draws: int, *, s: float,
               rng: np.random.Generator) -> np.ndarray:
    """Catalog ranks drawn with probability proportional to
    ``1 / (rank + 1) ** s``."""
    p = np.arange(1, n_items + 1, dtype=float) ** -s
    p /= p.sum()
    return rng.choice(n_items, size=n_draws, p=p)
