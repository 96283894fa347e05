"""Benchmark harness — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Outputs CSV blocks (name,value columns) and writes
artifacts/bench/<name>.csv.  Functions:

  fig5_ii        — II vs MII per CnKm, BandMap vs BusMap, ±GRF (Fig. 5)
  routing_pes    — routing-PE counts + reduction stats (§IV-B)
  mis_stats      — conflict-graph sizes / SBTS+repair solve stats (§III-B)
  ports          — allocated ports vs ceil(RD/M) (the §III-A policy)
  planner        — transfer-DFG bandwidth allocation per arch × shape,
                   predicted vs compiled collective bytes (beyond-paper)
  conflict_kernel— conflict-matrix build: bitset rows / Pallas kernel
                   vs python loops
  mis_engine     — bitset+portfolio engine vs seed dense engine
                   (details in artifacts/bench/bench_mis.json)
"""

from __future__ import annotations

import argparse
import csv
import io
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import (EXTRA_KERNELS, PAPER_KERNELS, cnkm_name,  # noqa: E402
                        make_cnkm, map_dfg)
from repro.core.cgra import CGRAConfig  # noqa: E402

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def _emit(name: str, header: list[str], rows: list[list]):
    buf = io.StringIO()
    w = csv.writer(buf)
    w.writerow(header)
    w.writerows(rows)
    text = buf.getvalue()
    print(f"\n== {name} ==")
    print(text)
    os.makedirs(os.path.join(ART, "bench"), exist_ok=True)
    with open(os.path.join(ART, "bench", f"{name}.csv"), "w") as f:
        f.write(text)
    return rows


def _map_all(kernels, grf: int, quick: bool):
    out = {}
    cgra = CGRAConfig(grf=grf)
    for (n, m) in kernels:
        for mode in ("bandmap", "busmap"):
            kw = dict(mis_restarts=4, mis_iters=8000, max_ii=8) \
                if quick else dict(max_ii=12)
            out[(n, m, mode)] = map_dfg(make_cnkm(n, m), cgra, mode=mode,
                                        **kw)
    return out


def bench_fig5_ii(quick: bool = False):
    """Fig. 5: realized II vs MII (ratio = MII/II; 1.0 is best)."""
    rows = []
    for grf in (0, 8):
        res = _map_all(PAPER_KERNELS, grf, quick)
        for (n, m) in PAPER_KERNELS:
            rb = res[(n, m, "bandmap")]
            ru = res[(n, m, "busmap")]
            rows.append([cnkm_name(n, m), grf, rb.mii, rb.ii, ru.ii,
                         f"{rb.ii_ratio:.2f}", f"{ru.ii_ratio:.2f}",
                         int(rb.ok), int(ru.ok)])
    return _emit("fig5_ii",
                 ["kernel", "grf", "mii", "bandmap_ii", "busmap_ii",
                  "bandmap_ratio", "busmap_ratio", "bandmap_ok",
                  "busmap_ok"], rows)


def bench_routing_pes(quick: bool = False):
    """§IV-B: routing-PE counts; reduction for m>4 kernels."""
    rows = []
    res = _map_all(PAPER_KERNELS, 0, quick)
    reductions = []
    for (n, m) in PAPER_KERNELS:
        rb, ru = res[(n, m, "bandmap")], res[(n, m, "busmap")]
        red = (1 - rb.n_routing_pes / ru.n_routing_pes) * 100 \
            if ru.n_routing_pes else 0.0
        if m > 4 and ru.n_routing_pes:
            reductions.append(red)
        rows.append([cnkm_name(n, m), m, rb.n_routing_pes,
                     ru.n_routing_pes, f"{red:.1f}"])
    avg = sum(reductions) / len(reductions) if reductions else 0.0
    rows.append(["avg_reduction_m>4", "", "", "", f"{avg:.1f}"])
    rows.append(["max_reduction_m>4", "", "", "",
                 f"{max(reductions, default=0):.1f}"])
    return _emit("routing_pes",
                 ["kernel", "m", "bandmap_routing", "busmap_routing",
                  "reduction_pct"], rows)


def bench_mis_stats(quick: bool = False):
    """§III-B: conflict-graph sizes and MIS solve effort."""
    rows = []
    for (n, m) in PAPER_KERNELS:
        for mode in ("bandmap", "busmap"):
            r = map_dfg(make_cnkm(n, m), CGRAConfig(), mode=mode,
                        mis_restarts=4 if quick else 10,
                        mis_iters=8000 if quick else 20000,
                        max_ii=8 if quick else 12)
            rows.append([cnkm_name(n, m), mode, r.cg_size[0], r.cg_size[1],
                         r.mis_size, r.n_ops, r.attempts,
                         f"{r.wall_s:.2f}"])
    return _emit("mis_stats",
                 ["kernel", "mode", "V_C", "E_C", "mis", "n_ops",
                  "attempts", "wall_s"], rows)


def bench_ports(quick: bool = False):
    """§III-A policy: allocated ports Q vs ceil(RD/M); the port-starved
    extra kernel (C8K6) exercises the routing fallback."""
    rows = []
    kernels = PAPER_KERNELS + ([] if quick else EXTRA_KERNELS)
    for (n, m) in kernels:
        r = map_dfg(make_cnkm(n, m), CGRAConfig(), mode="bandmap",
                    mis_restarts=4 if quick else 8,
                    mis_iters=8000, max_ii=8)
        q_policy = math.ceil(m / 4)
        total = sum(r.ports_per_vio.values())
        rows.append([cnkm_name(n, m), m, q_policy, total,
                     n * q_policy, r.n_routing_pes, int(r.ok)])
    return _emit("ports",
                 ["kernel", "RD", "ceil(RD/M)", "ports_allocated",
                  "policy_total", "routing_fallback", "ok"], rows)


def bench_planner(quick: bool = False):
    """Beyond-paper: planner transfer DFG per arch×shape; predicted vs
    compiled collective bytes (from the dry-run artifacts)."""
    from repro.configs import ARCHS, SHAPES, get_config
    from repro.core import planner as planner_mod

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    rows = []
    dr_dir = os.path.join(ART, "dryrun")
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape, cell in SHAPES.items():
            rec_path = os.path.join(dr_dir,
                                    f"{arch}__{shape}__single.json")
            compiled = None
            if os.path.exists(rec_path):
                with open(rec_path) as f:
                    rec = json.load(f)
                if not rec.get("skipped"):
                    compiled = rec["per_device"]["collective_bytes"]
            if compiled is None:
                continue
            plan = planner_mod.plan(cfg, cell.kind, cell.seq_len,
                                    cell.global_batch, FakeMesh(),
                                    arch=arch, shape=shape)
            top = max(plan.transfers, key=lambda t: t.bytes_per_step,
                      default=None)
            pred = plan.collective_bytes / 256    # per device
            rows.append([arch, shape, f"{pred:.3e}", f"{compiled:.3e}",
                         f"{pred / max(compiled, 1):.2f}",
                         top.tensor if top else "", top.rd if top else 0,
                         top.strategy if top else ""])
    return _emit("planner",
                 ["arch", "shape", "predicted_dev_bytes",
                  "compiled_dev_bytes", "ratio", "top_transfer", "rd",
                  "strategy"], rows)


def bench_conflict_kernel(quick: bool = False):
    """Conflict-matrix construction: packed-bitset rows (the engine's
    path) and the vectorised Pallas kernel vs python loops (the
    O(|V_C|²) hot spot)."""
    from repro.core import schedule_dfg
    from repro.core.conflict import (bitset_group_conflicts,
                                     build_conflict_graph,
                                     dense_conflicts_python)
    from repro.kernels.conflict_matrix.ops import conflict_matrix
    rows = []
    for (n, m) in [(2, 6), (5, 5), (4, 8)]:
        sched = schedule_dfg(make_cnkm(n, m), CGRAConfig())
        cg = build_conflict_graph(sched, CGRAConfig())
        t0 = time.perf_counter()
        for _ in range(3):
            bitset_group_conflicts(cg.vertices, cg.op_vertices, sched.ii)
        t_bits = (time.perf_counter() - t0) / 3
        t0 = time.perf_counter()
        for _ in range(3):
            conflict_matrix(cg.vertices)
        t_fast = (time.perf_counter() - t0) / 3
        t0 = time.perf_counter()
        dense_conflicts_python(cg.vertices, cg.op_vertices, sched.ii)
        t_slow = time.perf_counter() - t0
        rows.append([cnkm_name(n, m), cg.n, f"{t_bits*1e3:.2f}",
                     f"{t_fast*1e3:.2f}", f"{t_slow*1e3:.2f}",
                     f"{t_slow/t_bits:.1f}x"])
    return _emit("conflict_kernel",
                 ["kernel", "V_C", "bitset_ms", "vectorised_ms",
                  "python_ms", "bitset_speedup"], rows)


def bench_mis_engine(quick: bool = False):
    """Bitset + portfolio engine benchmark (full detail in
    artifacts/bench/bench_mis.json)."""
    from benchmarks.bench_mis import run_all
    bench = run_all(quick=quick)
    sp = bench["engine_speedup"]
    rows = [["engine_speedup_c5k5_ii2", sp["speedup"]],
            ["bitset_build_s", sp["bitset_build_s"]],
            ["seed_build_s", sp["seed_build_s"]],
            ["bitset_solve_s", sp["bitset_solve_s"]],
            ["seed_solve_s", sp["seed_solve_s"]]]
    for row in bench["straggler"]:
        rows.append([f"straggler_{row['kernel']}_{row['mode']}_wall_s",
                     row["wall_s"]])
        rows.append([f"straggler_{row['kernel']}_{row['mode']}_"
                     f"cert_total_s", row["cert_total_s"]])
    for row in bench["exact"]:
        rows.append([f"exact_{row['kernel']}_{row['mode']}_wall_s",
                     row["exact_wall_s"]])
        rows.append([f"exact_{row['kernel']}_{row['mode']}_gap",
                     row["gap"]])
        rows.append([f"race_{row['kernel']}_{row['mode']}_winner",
                     row["race_winner"]])
    for row in bench["cgra_8x8"]:
        rows.append([f"map8x8_{row['kernel']}_{row['mode']}_wall_s",
                     row["wall_s"]])
    for row in bench["comap"]:
        rows.append([f"{row['mode']}_{row['kernel']}_wall_s",
                     row["wall_s"]])
    for row in bench["group_move"]:
        rows.append([f"group_move_{row['kernel']}_{row['mode']}_wall_s",
                     row["wall_s"]])
        cov = row.get("coverage")
        if isinstance(cov, dict):
            last = sorted(cov, key=int)[-1]
            rows.append([f"group_move_{row['kernel']}_{row['mode']}_"
                         f"coverage@{last}", f"{cov[last]}/{row['n_ops']}"])
    for row in bench["device_engine"]:
        rows.append([f"device_{row['kernel']}_{row['mode']}_wall_s",
                     row["wall_s"]])
        rows.append([f"device_{row['kernel']}_{row['mode']}_coverage",
                     row["coverage"]])
    for row in bench["serve"]:
        rows.append([f"serve_{row['kernel']}_{row['mode']}_rps",
                     row["rps"]])
        if "hit_rate" in row:
            rows.append([f"serve_{row['kernel']}_{row['mode']}_hit_rate",
                         row["hit_rate"]])
        if "speedup" in row:
            rows.append([f"serve_{row['kernel']}_{row['mode']}_speedup",
                         row["speedup"]])
    return _emit("mis_engine", ["name", "value"], rows)


BENCHES = {
    "fig5_ii": bench_fig5_ii,
    "routing_pes": bench_routing_pes,
    "mis_stats": bench_mis_stats,
    "ports": bench_ports,
    "planner": bench_planner,
    "conflict_kernel": bench_conflict_kernel,
    "mis_engine": bench_mis_engine,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        fn(quick=args.quick)


if __name__ == "__main__":
    main()
