"""Plain reference: replay a mapping verdict against the fabric model.

Independent of the mapper: it reads the request graph the benchmark
built (`dfggen.Graph`), the fabric as the configuration states it, and
the verdict as plain data (`Binding`), and checks every rule of the
fabric model from scratch.  It computes MII itself and re-counts the
routing PEs; it trusts no number the mapper reports.

Fabric model (the BandMap paper's bus-based CGRA): a ``rows`` x
``cols`` PE array; row r has input port IPORT_r driving bus (row, r, 0)
and column c has output port OPORT_c draining bus (col, c, 0); every
row and column scope has ``buses_per_scope`` buses with one driver per
bus per cycle; a PE reads its row's and its column's buses, and its
four neighbours' output registers; every PE has ``lrf`` local registers
and the array one global register file of ``grf`` entries.  A modulo
schedule with initiation interval II repeats every II cycles, so every
resource is counted per modulo slot.

A binding passes when:

1. the scheduled graph implements the request: every request op is
   present with its kind, extra ops are only copies of an input (one
   port each) or routing ops, and collapsing copies and routing ops
   gives back exactly the request's edges with their distances;
2. every edge meets its latency: ``t[dst] + dist * II >= t[src] + lat``;
3. every op is placed once, on a resource of its kind, at its own
   scheduled slot, and no PE, IPORT or OPORT is used twice in a slot;
4. every edge is routable: an input on port r reaches only row r (a
   GRF-parked input reaches every PE), an output on port c is fed from
   column c, and a PE-to-PE transfer uses the same PE, a neighbour link
   (plain producers only), or a bus of a scope both ends share (a
   routing op only over the scope it drives);
5. every bus transfer has a (bus, cycle) in its hold window and no bus
   cell has two drivers, counting the ports' fixed drives;
6. the LRF of every PE and the GRF hold what the schedule parks there
   in every slot;
7. II lies in [MII, max_ii], the reported MII is the reference's, and
   the reported routing-PE count is the number of routing ops.

A certified "no II binds" verdict passes when it carries a certificate
for every II from MII to ``max_ii``.
"""

from __future__ import annotations

import dataclasses
import math
import numbers

from . import dfggen

ROW, COL = "row", "col"
TIN, TOUT, QUAD = "tin", "tout", "quad"


@dataclasses.dataclass(frozen=True)
class Fabric:
    rows: int = 4
    cols: int = 4
    lrf: int = 8
    grf: int = 0
    buses_per_scope: int = 2

    @property
    def n_pes(self) -> int:
        return self.rows * self.cols


@dataclasses.dataclass
class Binding:
    """A verdict's binding as plain data.  ``place[op]`` is a dict with
    ``kind`` (tin/tout/quad), ``t``, ``m``, ``port``, ``mode``
    (tin: bus/grf), ``pe`` ((r, c) or None) and ``drive`` ((scope, idx)
    or None); ``buses[(src, dst)]`` is ``(scope, idx, bus, slot)``."""
    ii: int
    ops: dict            # id -> dfggen.Op (kind, name, latency, clone_of)
    edges: list          # (src, dst, distance)
    time: dict           # id -> scheduled cycle
    delivery: dict       # input id -> "bus" | "grf"
    place: dict
    buses: dict


@dataclasses.dataclass
class Verdict:
    """What one request came back with.  ``binding`` is None for a
    verdict that claims no II up to ``max_ii`` binds; ``certified_iis``
    lists the IIs its certificates cover."""
    ok: bool
    ii: int
    mii: int
    routing_pes: int
    binding: Binding | None = None
    proved_infeasible: bool = False
    certified_iis: tuple = ()


# ----------------------------------------------------------------- MII
def ref_mii(g: dfggen.Graph, fab: Fabric) -> int:
    """max(resource MII, recurrence MII), the recurrence bound found as
    the least II with no cycle of positive ``sum(lat) - II * sum(dist)``
    (Bellman-Ford, exact for any cycle length)."""
    kinds = [op.kind for op in g.ops.values()]
    res = max(math.ceil(sum(k in (dfggen.COMPUTE, dfggen.ROUTE)
                            for k in kinds) / fab.n_pes),
              math.ceil(kinds.count(dfggen.VIN) / fab.rows),
              math.ceil(kinds.count(dfggen.VOUT) / fab.cols), 1)
    total_lat = sum(op.latency for op in g.ops.values())
    ii = 1
    while ii <= total_lat and _positive_cycle(g, ii):
        ii += 1
    return max(res, ii)


def _positive_cycle(g: dfggen.Graph, ii: int) -> bool:
    ids = list(g.ops)
    dist = {i: 0 for i in ids}
    w = [(s, t, g.ops[s].latency - ii * d) for s, t, d in g.edges]
    for _ in range(len(ids)):
        changed = False
        for s, t, wt in w:
            if dist[s] + wt > dist[t]:
                dist[t] = dist[s] + wt
                changed = True
        if not changed:
            return False
    return True


# --------------------------------------------------------- the replay
def check_verdict(g: dfggen.Graph, fab: Fabric, max_ii: int,
                  v: Verdict) -> list[str]:
    """All violations of one verdict for request ``g`` (empty = pass)."""
    mii = ref_mii(g, fab)
    out: list[str] = []
    if v.mii != mii:
        out.append(f"reported MII {v.mii} != reference MII {mii}")
    if not v.ok:
        if not v.proved_infeasible:
            out.append("no binding and no infeasibility proof")
        missing = [ii for ii in range(mii, max_ii + 1)
                   if ii not in set(v.certified_iis)]
        if missing:
            out.append(f"infeasibility proof misses II {missing}")
        return out
    b = v.binding
    if b is None:
        return out + ["binding verdict without a binding"]
    if v.ii != b.ii:
        out.append(f"reported II {v.ii} != binding II {b.ii}")
    if not mii <= b.ii <= max_ii:
        out.append(f"II {b.ii} outside [{mii}, {max_ii}]")
    n_route = sum(op.kind == dfggen.ROUTE for op in b.ops.values())
    if v.routing_pes != n_route:
        out.append(f"reported routing PEs {v.routing_pes} != {n_route}")
    out += _check_graph(g, b)
    if out:
        return out
    out += _check_times(b)
    out += _check_place(fab, b)
    if out:
        return out
    out += _check_routes_and_buses(fab, b)
    out += _check_registers(fab, b)
    return out


def _check_graph(g: dfggen.Graph, b: Binding) -> list[str]:
    out = [f"edge {s}->{t} between unknown ops" for s, t, _ in b.edges
           if s not in b.ops or t not in b.ops]
    for oid, op in g.ops.items():
        got = b.ops.get(oid)
        if got is None or got.kind != op.kind or got.latency != op.latency:
            out.append(f"request op {oid} ({op.kind}) missing or changed")
    group = {}
    for oid, op in b.ops.items():
        if oid in g.ops:
            group[oid] = oid
        elif op.kind == dfggen.VIN:
            root = op.clone_of
            if root not in g.ops or g.ops[root].kind != dfggen.VIN:
                out.append(f"input copy {oid} of unknown input {root}")
            group[oid] = root
        elif op.kind != dfggen.ROUTE:
            out.append(f"extra op {oid} of kind {op.kind}")
    if out:
        return out
    preds: dict[int, list] = {}
    for s, t, d in b.edges:
        preds.setdefault(t, []).append((s, d))

    def source(oid: int, dist: int, depth: int = 0):
        """Request-level source of a value arriving from ``oid``."""
        if b.ops[oid].kind != dfggen.ROUTE:
            return group[oid], dist
        ins = preds.get(oid, [])
        if len(ins) != 1 or depth > len(b.ops):
            return None, dist
        s, d = ins[0]
        return source(s, dist + d, depth + 1)

    logical = []
    for s, t, d in b.edges:
        if b.ops[t].kind == dfggen.ROUTE:
            continue
        src, dist = source(s, d)
        if src is None:
            out.append(f"routing op feeding {t} has not exactly one input")
            continue
        logical.append((src, t, dist))
    if sorted(logical) != sorted(g.edges):
        extra = sorted(set(logical) - set(g.edges))[:4]
        lost = sorted(set(g.edges) - set(logical))[:4]
        out.append(f"scheduled edges differ from the request: "
                   f"extra {extra}, missing {lost}")
    for oid, op in b.ops.items():
        if op.kind == dfggen.ROUTE and not any(s == oid
                                               for s, _, _ in b.edges):
            out.append(f"routing op {oid} feeds nothing")
    return out


def _check_times(b: Binding) -> list[str]:
    out = []
    for oid in b.ops:
        t = b.time.get(oid)
        if not isinstance(t, numbers.Integral) or t < 0:
            out.append(f"op {oid} has no valid time ({t!r})")
    if out:
        return out
    for s, t, d in b.edges:
        if b.time[t] + d * b.ii < b.time[s] + b.ops[s].latency:
            out.append(f"edge {s}->{t} (distance {d}) used at "
                       f"{b.time[t] + d * b.ii} before ready at "
                       f"{b.time[s] + b.ops[s].latency}")
    return out


def _check_place(fab: Fabric, b: Binding) -> list[str]:
    out = []
    if set(b.place) != set(b.ops):
        out.append(f"placed ops differ from scheduled ops: "
                   f"{sorted(set(b.ops) ^ set(b.place))[:6]}")
        return out
    used = {}
    for oid, p in b.place.items():
        kind = b.ops[oid].kind
        t = b.time[oid]
        if p["t"] != t or p["m"] != t % b.ii:
            out.append(f"op {oid} placed at t={p['t']} m={p['m']}, "
                       f"scheduled t={t}")
        if kind == dfggen.VIN:
            ok = (p["kind"] == TIN and 0 <= p["port"] < fab.rows
                  and p["mode"] == b.delivery.get(oid)
                  and p["mode"] in ("bus", "grf")
                  and (p["mode"] == "bus" or fab.grf > 0))
            key = ("iport", p["port"], t % b.ii)
        elif kind == dfggen.VOUT:
            ok = p["kind"] == TOUT and 0 <= p["port"] < fab.cols
            key = ("oport", p["port"], t % b.ii)
        else:
            pe = p["pe"]
            ok = (p["kind"] == QUAD and pe is not None
                  and 0 <= pe[0] < fab.rows and 0 <= pe[1] < fab.cols)
            if ok and kind == dfggen.ROUTE:
                ok = p["drive"] in ((ROW, pe[0]), (COL, pe[1]))
            elif ok:
                ok = p["drive"] is None
            key = ("pe", tuple(pe) if pe is not None else None, t % b.ii)
        if not ok:
            out.append(f"op {oid} ({kind}) on a wrong resource: {p}")
        if key in used:
            out.append(f"{key} used by ops {used[key]} and {oid}")
        used[key] = oid
    return out


def _window_slots(t_ready: int, t_use: int, ii: int) -> set[int]:
    return {t % ii for t in range(t_ready, min(t_use, t_ready + ii - 1) + 1)}


def _check_routes_and_buses(fab: Fabric, b: Binding) -> list[str]:
    out = []
    ii = b.ii
    cells = {}      # (scope, idx, bus, slot) -> driver op

    def drive(cell, driver, what):
        if cell in cells and cells[cell] != driver:
            out.append(f"bus {cell} driven by {cells[cell]} and {driver} "
                       f"({what})")
        cells.setdefault(cell, driver)

    for oid, p in b.place.items():
        if p["kind"] == TIN and p["mode"] == "bus":
            drive((ROW, p["port"], 0, p["m"]), ("port", oid), "input port")
        elif p["kind"] == TOUT:
            drive((COL, p["port"], 0, p["m"]), ("port", oid), "output port")
    for s, t, d in b.edges:
        ps, pt = b.place[s], b.place[t]
        t_ready = b.time[s] + b.ops[s].latency
        t_use = b.time[t] + d * ii
        if ps["kind"] == TIN:
            if pt["kind"] != QUAD:
                out.append(f"input {s} feeds non-PE op {t}")
            elif ps["mode"] == "bus" and pt["pe"][0] != ps["port"]:
                out.append(f"input {s} on row {ps['port']} cannot reach "
                           f"{t} at {pt['pe']}")
            continue
        if pt["kind"] == TOUT:
            if ps["kind"] != QUAD or ps["pe"][1] != pt["port"]:
                out.append(f"output {t} on column {pt['port']} cannot be "
                           f"fed from {s} at {ps['pe']}")
            continue
        if pt["kind"] != QUAD:
            out.append(f"edge {s}->{t} between non-PE ops")
            continue
        a, c = tuple(ps["pe"]), tuple(pt["pe"])
        if a == c:
            continue
        if ps["drive"] is None and abs(a[0] - c[0]) + abs(a[1] - c[1]) == 1:
            continue
        if ps["drive"] is not None:
            scope, idx = ps["drive"]
            shared = [(scope, idx)] if (
                (scope == ROW and c[0] == idx)
                or (scope == COL and c[1] == idx)) else []
        else:
            shared = ([(ROW, a[0])] if a[0] == c[0] else []) + \
                     ([(COL, a[1])] if a[1] == c[1] else [])
        if not shared:
            out.append(f"edge {s}->{t}: {a} cannot reach {c}")
            continue
        cell = b.buses.get((s, t))
        if cell is None:
            out.append(f"edge {s}->{t} has no bus assignment")
            continue
        scope, idx, bus, slot = cell
        if (scope, idx) not in shared or not 0 <= bus < fab.buses_per_scope \
                or slot not in _window_slots(t_ready, t_use, ii):
            out.append(f"edge {s}->{t}: bus cell {cell} outside scopes "
                       f"{shared} or window [{t_ready}, {t_use}]")
            continue
        drive(cell, ("op", s), f"edge {s}->{t}")
    return out


def _slots(a: int, b: int, ii: int) -> dict[int, int]:
    """How often each modulo slot occurs among cycles a..b."""
    out: dict[int, int] = {}
    for t in range(a, b + 1):
        out[t % ii] = out.get(t % ii, 0) + 1
    return out


def _check_registers(fab: Fabric, b: Binding) -> list[str]:
    ii = b.ii
    lrf: dict[tuple, dict[int, int]] = {}

    def hold(pe, a, z):
        slot = lrf.setdefault(tuple(pe), {})
        for s, c in _slots(a, z, ii).items():
            slot[s] = slot.get(s, 0) + c

    for oid, p in b.place.items():
        if b.ops[oid].kind == dfggen.COMPUTE:
            hold(p["pe"], 0, ii - 1)           # the op's resident constant
    for s, t, d in b.edges:
        ps, pt = b.place[s], b.place[t]
        t_src = b.time[s]
        t_use = b.time[t] + d * ii
        t_ready = t_src + b.ops[s].latency
        if ps["kind"] == TIN:
            if ps["mode"] == "bus" and pt["kind"] == QUAD:
                hold(pt["pe"], t_src, t_use)   # latched at delivery
        elif pt["kind"] == TOUT:
            hold(ps["pe"], t_ready, t_use)     # held until the export
        elif tuple(ps["pe"]) == tuple(pt["pe"]):
            hold(ps["pe"], t_ready, t_use)
        else:
            cell = b.buses.get((s, t))
            t_drive = t_ready + (((cell[3] if cell else t_ready) - t_ready)
                                 % ii)
            hold(ps["pe"], t_ready, t_drive)   # producer holds to the drive
            hold(pt["pe"], t_drive, t_use)     # consumer latches after it
    out = [f"LRF of PE {pe} holds {max(v.values())} > {fab.lrf}"
           for pe, v in lrf.items() if v and max(v.values()) > fab.lrf]
    grf: dict[int, int] = {}
    for oid, p in b.place.items():
        if p["kind"] == TIN and p["mode"] == "grf":
            t0 = b.time[oid]
            t1 = max((b.time[t] + d * ii for s, t, d in b.edges if s == oid),
                     default=t0)
            for s, c in _slots(t0, t1, ii).items():
                grf[s] = grf.get(s, 0) + c
    if grf and max(grf.values()) > fab.grf:
        out.append(f"GRF holds {max(grf.values())} > {fab.grf}")
    return out
