"""Frozen reference for `repro.core.mis.ejection_repair`: the ejection-
chain search over unpacked 0/1 adjacency rows and numpy membership
arrays, kept only as the oracle of ``tests/test_ejection_repair.py``.

It is the search as it ran before the bitmask rewrite, unchanged except
that it also returns its search-node count.  Do not optimise or fix it:
the point is that the program's version gives the same answers.
"""

from __future__ import annotations

import numpy as np

from repro.core.bitset import as_bitset_graph, pack_bool


def ejection_repair_ref(adj, in_s: np.ndarray,
                        op_vertices: dict[int, list[int]],
                        op_of: np.ndarray, *, depth: int = 3,
                        seed: int = 0) -> tuple[np.ndarray, int]:
    """(repaired membership, search nodes visited)."""
    g = as_bitset_graph(adj)
    rng = np.random.default_rng(seed)
    in_s = in_s.copy()
    conf = g.conflict_counts(pack_bool(in_s))
    u8 = g.rows_u8(np.arange(g.n)) if g.n \
        else np.zeros((0, 0), dtype=np.uint8)
    doms = {op: np.asarray(ids, dtype=np.int64)
            for op, ids in op_vertices.items()}
    banned = np.zeros(g.n, dtype=bool)
    nodes = [0]

    def place(op: int, d: int) -> bool:
        nonlocal conf
        nodes[0] += 1
        if nodes[0] > 20000:
            return False
        dom = doms[op]
        alive = dom[~(in_s[dom] | banned[dom])]
        if alive.size == 0:
            return False
        order = np.argsort(conf[alive] + rng.random(alive.size),
                           kind="stable")
        cands = alive[order]
        n_evict = conf[cands]
        for v, ne in zip(cands, n_evict):
            if ne == 0:
                in_s[v] = True
                conf += u8[v]
                return True
            if d == 0 or ne > 2:
                continue
            evict = np.flatnonzero(u8[v] & in_s)
            evicted_ops = [int(op_of[u]) for u in evict]
            in_s_snap, conf_snap = in_s.copy(), conf.copy()
            for u in evict:
                in_s[u] = False
                conf -= u8[u]
            in_s[v] = True
            conf += u8[v]
            banned[v] = True
            if all(place(eo, d - 1) for eo in evicted_ops):
                banned[v] = False
                return True
            banned[v] = False
            in_s[:] = in_s_snap
            conf = conf_snap
        return False

    placed_ops = {int(op_of[v]) for v in np.flatnonzero(in_s)}
    for op in op_vertices:
        if op not in placed_ops:
            if place(op, depth):
                placed_ops.add(op)
    assert not g.any_conflict(pack_bool(in_s)), "repair broke independence"
    return in_s, nodes[0]
