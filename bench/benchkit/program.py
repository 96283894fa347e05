"""The one module of the benchmark that touches the system under test.

It turns a request graph into the program's own DFG, calls the entry
the traffic names, and turns the program's result back into the plain
`reference.Verdict` the reference checks.  Nothing else of the program
is read: no weights, tables or helpers feed the reference.
"""

from __future__ import annotations

from . import dfggen
from .reference import Binding, Fabric, Verdict


def program_dfg(g: dfggen.Graph):
    """The request as the program's `DFG`, with the same op ids and the
    same op and edge order."""
    from repro.core.dfg import DFG, Edge, Op, OpKind
    d = DFG()
    for oid, op in g.ops.items():
        d.ops[oid] = Op(oid, OpKind(op.kind), op.name, op.latency,
                        op.clone_of)
    d.edges = [Edge(s, t, dist) for s, t, dist in g.edges]
    d._next_id = g.next_id
    return d


def program_fabric(fab: Fabric):
    from repro.core.cgra import CGRAConfig
    return CGRAConfig(rows=fab.rows, cols=fab.cols, lrf=fab.lrf,
                      grf=fab.grf, buses_per_scope=fab.buses_per_scope)


def map_request(g: dfggen.Graph, cgra, options: dict, *, tracer=None):
    """One request through `map_dfg`, the program's mapping entry."""
    from repro.core.bandmap import map_dfg
    return map_dfg(program_dfg(g), cgra, dict(options), tracer=tracer)


def verdict(res) -> Verdict:
    """The program's `MappingResult` as plain data."""
    binding = None
    if res.ok and res.sched is not None:
        sched = res.sched
        ops = {oid: dfggen.Op(op.kind.value, op.name, op.latency,
                              op.clone_of)
               for oid, op in sched.dfg.ops.items()}
        place = {}
        for oid, v in res.placement.items():
            place[oid] = {
                "kind": v.kind, "t": v.t, "m": v.m, "port": v.port,
                "mode": v.mode,
                "pe": tuple(v.pe) if v.kind == "quad" else None,
                "drive": tuple(v.drive) if v.drive is not None else None}
        buses = dict(res.report.bus_assignment) if res.report else {}
        binding = Binding(
            ii=sched.ii, ops=ops,
            edges=[(e.src, e.dst, e.distance) for e in sched.dfg.edges],
            time=dict(sched.time), delivery=dict(sched.delivery),
            place=place, buses=buses)
    return Verdict(ok=bool(res.ok), ii=int(res.ii), mii=int(res.mii),
                   routing_pes=int(res.n_routing_pes), binding=binding,
                   proved_infeasible=bool(res.proved_infeasible),
                   certified_iis=tuple(sorted({c.ii for c in
                                               res.certificates})))


def has_verdict(res) -> bool:
    """A binding, or a proof that no II up to ``max_ii`` binds; a search
    that gave up is no verdict."""
    return bool(res.ok or res.proved_infeasible)


def device_shapes(g: dfggen.Graph, cgra, options: dict) -> set[int]:
    """Padded vertex counts of the device engine's programs over every
    (II, jitter) schedule the request can reach, for the warm-up."""
    from repro.core.mis_device import _pad_n
    from repro.core.options import MapOptions
    from repro.core.schedule import mii, schedule_dfg
    opts = MapOptions.coerce(dict(options), {})
    d = program_dfg(g)
    per_kind = {"vin": cgra.rows, "vout": cgra.cols,
                "compute": cgra.n_pes, "route": 2 * cgra.n_pes}
    shapes = set()
    for ii in range(mii(d, cgra), opts.schedule.max_ii + 1):
        for jitter in range(4):
            try:
                s = schedule_dfg(d, cgra, mode=opts.mode, ii=ii, max_ii=ii,
                                 use_grf=opts.schedule.use_grf,
                                 jitter=jitter, seed=opts.seed,
                                 max_bus_fanout=opts.schedule.max_bus_fanout)
            except RuntimeError:
                continue
            n = sum(per_kind[op.kind.value] for op in s.dfg.ops.values())
            shapes.add(_pad_n(n))
    return shapes


def warm_device(n_pad: int, options: dict) -> None:
    """Compile and run one chunk of the device engine's program at
    ``n_pad`` vertices, on an empty graph."""
    from repro.core.bitset import BitsetGraph
    from repro.core.mis_device import DeviceSBTS
    from repro.core.options import MapOptions
    opts = MapOptions.coerce(dict(options), {})
    eng = DeviceSBTS(BitsetGraph(n_pad), None, k=opts.portfolio.device_seeds)
    eng.run(eng.chunk_size)
