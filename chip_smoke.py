#!/usr/bin/env python3
"""Smoke run of the mapper's device path on one TPU.

Drives the entry points a user calls — `map_dfg` with
``engine="device"`` and `MappingService.map_batch` — once, at the fabric
sizes the benchmark uses, plus the two Pallas kernels on their own
against their references:

  (a) device        the first JAX device must be a TPU
  (b) counts        `selection_counts_pallas` at n_pad 10496, K 1024 on
                    seeded random packed rows == the numpy reference
  (c) conflict      the packed Pallas conflict build of the 16x16 loop
                    kernel's schedule == the host bitset build, byte
                    for byte
  (d) device maps   `map_dfg(engine="device")` on C4K8@8x8 and
                    loop40@16x16 against the numpy engine: validator-
                    accepted, device II <= numpy II
  (e) served        a cold `MappingService` batch of device-engine
                    requests: paper kernels at their golden (II, routing
                    PE) pairs, every result validated, no crash

Any failed check raises and the run exits non-zero; so does a run where
any kernel ran in interpret mode or any traced span recorded a
swallowed error.  Every timing line is a TPU measurement of one
unrepeated run, not a benchmark.  The last stdout line is the JSON
verdict ``{"ok": true, "device": {...}}``.

  python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "tests"))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Counts XLA compiles (persistent-cache loads included) and their
    seconds through JAX's monitoring hooks."""

    def __init__(self) -> None:
        import jax
        self.count = 0
        self.secs = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.secs += duration

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def run_phase(name: str, log: CompileLog, fn, *args, **kwargs):
    c0, s0, h0 = log.count, log.secs, log.cache_hits
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    print(f"[tpu] phase {name}: wall {wall:.3f} s; compiles "
          f"{log.count - c0} ({log.secs - s0:.3f} s, cache hits "
          f"{log.cache_hits - h0})", flush=True)
    return out


def check_spans(tracer, where: str, *, interpret: bool) -> int:
    """Fail on a span that recorded an exception (an error some caller
    swallowed, such as a crashed race side) or a kernel that ran in the
    wrong Pallas mode; returns the number of device SBTS rounds."""
    rounds = 0
    for sp in tracer.finished:
        if "error" in sp.attrs:
            raise AssertionError(f"{where}: span {sp.name!r} recorded "
                                 f"error {sp.attrs['error']}")
        if "interpret" in sp.attrs and sp.attrs["interpret"] != interpret:
            raise AssertionError(f"{where}: span {sp.name!r} ran with "
                                 f"interpret={sp.attrs['interpret']}")
        rounds += sp.name == "portfolio-device"
    return rounds


# ------------------------------------------------------------- phases
def phase_device() -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[tpu] device: platform {dev['platform']}, kind "
          f"{dev['kind']!r}, count {dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"phase (a): first device is {dev['platform']!r}"
                         ", not a TPU")
    return dev


def phase_counts(seed: int, *, n_pad: int = 10496, k: int = 1024,
                 interpret: bool = False) -> None:
    import jax

    from repro.kernels.sbts_step.kernel import selection_counts_pallas
    from repro.kernels.sbts_step.ref import selection_counts_ref

    rng = np.random.default_rng(seed)
    w = n_pad // 32
    rows = rng.integers(0, 1 << 32, (n_pad, w), dtype=np.uint32)
    sel = rng.integers(0, 1 << 32, (k, w), dtype=np.uint32)
    t0 = time.perf_counter()
    out = selection_counts_pallas(rows, sel, interpret=interpret)
    out = np.asarray(jax.block_until_ready(out))
    print(f"[tpu]   selection_counts_pallas n_pad {n_pad} K {k}: first "
          f"call {time.perf_counter() - t0:.3f} s", flush=True)
    t0 = time.perf_counter()
    jax.block_until_ready(selection_counts_pallas(rows, sel,
                                                  interpret=interpret))
    print(f"[tpu]   selection_counts_pallas second call "
          f"{time.perf_counter() - t0:.6f} s", flush=True)
    step = max(1, k // 64)
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        ref = np.concatenate(list(pool.map(
            lambda k0: selection_counts_ref(rows, sel[k0:k0 + step]),
            range(0, k, step))))
    if out.shape != ref.shape or not np.array_equal(out, ref):
        bad = int((out != ref).sum()) if out.shape == ref.shape else -1
        raise AssertionError(f"phase (b): {bad} counts differ from the "
                             "reference")


def c4k8_8x8():
    from repro.core import CGRAConfig, make_cnkm
    from repro.core.options import MapOptions
    return make_cnkm(4, 8), CGRAConfig(rows=8, cols=8), MapOptions()


def loop16x16():
    from repro.core import CGRAConfig, scale_16x16_loop
    from repro.core.options import MapOptions, ScheduleOptions
    return (scale_16x16_loop(), CGRAConfig(rows=16, cols=16),
            MapOptions(schedule=ScheduleOptions(max_ii=8,
                                                max_bus_fanout=4)))


def phase_conflict(case=loop16x16, *, interpret: bool = False) -> None:
    from repro.core.conflict import build_conflict_graph
    from repro.core.schedule import mii, schedule_dfg
    from repro.obs.trace import Tracer

    dfg, cgra, opts = case()
    ii = mii(dfg, cgra)
    sched = schedule_dfg(dfg, cgra, mode=opts.mode, ii=ii, max_ii=ii,
                         use_grf=opts.schedule.use_grf, jitter=0,
                         seed=opts.seed,
                         max_bus_fanout=opts.schedule.max_bus_fanout)
    tracer = Tracer()
    t0 = time.perf_counter()
    dev = build_conflict_graph(sched, cgra, use_kernel="packed-pallas",
                               bus_pressure=opts.bus_pressure,
                               tracer=tracer)
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    host = build_conflict_graph(sched, cgra,
                                bus_pressure=opts.bus_pressure)
    t_host = time.perf_counter() - t0
    check_spans(tracer, "phase (c)", interpret=interpret)
    print(f"[tpu]   conflict build |V_C| {host.n} at II {ii}: packed-"
          f"pallas {t_dev:.3f} s, host bitset {t_host:.3f} s",
          flush=True)
    if dev.bits.rows.tobytes() != host.bits.rows.tobytes():
        raise AssertionError("phase (c): packed-pallas adjacency rows "
                             "differ from the host build")


def _validated(res, cgra, where: str) -> None:
    from repro.core.validate import validate_mapping
    report = validate_mapping(res.sched, cgra, res.placement)
    if not report.ok:
        raise AssertionError(f"{where}: validator rejects the mapping: "
                             f"{report}")


def phase_maps(cases, *, device_seeds: int = 1024,
               interpret: bool = False) -> None:
    from repro.core import map_dfg
    from repro.obs.trace import Tracer

    device_iters = 0
    for name, case in cases:
        dfg, cgra, opts = case()
        got = {}
        for engine in ("device", "numpy"):
            eopts = opts.replace(engine=engine, backend="portfolio",
                                 device_seeds=device_seeds)
            tracer = Tracer()
            t0 = time.perf_counter()
            res = map_dfg(dfg, cgra, eopts, tracer=tracer)
            wall = time.perf_counter() - t0
            rounds = check_spans(tracer, f"phase (d) {name}",
                                 interpret=interpret)
            print(f"[tpu]   {name} {engine}: ok {res.ok}, II {res.ii}, "
                  f"MII {res.mii}, routing PEs {res.n_routing_pes}, "
                  f"wall {wall:.3f} s, device rounds {rounds}, portfolio "
                  f"iters {tracer.counter_value('portfolio.iters')}",
                  flush=True)
            if engine == "device":
                device_iters += tracer.counter_value("portfolio.iters")
                print("[tpu]     phases (self s / total s): " + ", ".join(
                    f"{ph} {v['self_s']:.3f}/{v['total_s']:.3f} "
                    f"x{v['count']}"
                    for ph, v in tracer.phase_breakdown().items()),
                    flush=True)
            if not res.ok:
                raise AssertionError(f"phase (d) {name} {engine}: "
                                     f"{res.summary()}")
            _validated(res, cgra, f"phase (d) {name} {engine}")
            got[engine] = res
        if got["device"].ii > got["numpy"].ii:
            raise AssertionError(f"phase (d) {name}: device II "
                                 f"{got['device'].ii} > numpy II "
                                 f"{got['numpy'].ii}")
    if not device_iters:
        raise AssertionError("phase (d): the device engine ran no SBTS "
                             "step on any map")


PAPER =((2, 6), (3, 6), (4, 4))
BIG = ((4, 8), (5, 5))


def phase_serve(*, paper=PAPER, big=BIG, big_fabric: int = 8,
                interpret: bool = False) -> None:
    from test_golden_results import GOLDEN

    from repro.core import CGRAConfig, cnkm_name, make_cnkm
    from repro.serve import MappingService
    from repro.serve.scheduler import MapRequest

    small, large = CGRAConfig(), CGRAConfig(rows=big_fabric,
                                            cols=big_fabric)
    reqs, golden = [], {}
    for n, m in paper:
        for mode in ("bandmap", "busmap"):
            rid = f"{cnkm_name(n, m)}:{mode}"
            golden[rid] = GOLDEN[(n, m, mode)]
            reqs.append(MapRequest(dfg=make_cnkm(n, m), cgra=small,
                                   options={"engine": "device",
                                            "mode": mode},
                                   req_id=rid))
    for n, m in big:
        reqs.append(MapRequest(
            dfg=make_cnkm(n, m), cgra=large, options={"engine": "device"},
            req_id=f"{cnkm_name(n, m)}@{big_fabric}x{big_fabric}"))
    svc = MappingService(art_dir=None, trace_sample=1.0)
    outs = svc.map_batch(reqs)
    tracers = dict(svc.traces)
    for req, out in zip(reqs, outs):
        tracer = tracers.get(out.canon_digest)
        iters = tracer.counter_value("portfolio.iters") if tracer else 0
        print(f"[tpu]   served {out.req_id}: source {out.source}, ok "
              f"{out.ok}, II {out.result.ii}, routing PEs "
              f"{out.result.n_routing_pes}, latency {out.wall_s:.3f} s, "
              f"device SBTS iters {iters}", flush=True)
        if out.source == "crash":
            raise AssertionError(f"phase (e) {out.req_id} crashed: "
                                 f"{out.result.flight}")
        if not out.ok:
            raise AssertionError(f"phase (e) {out.req_id}: "
                                 f"{out.result.summary()}")
        _validated(out.result, req.cgra, f"phase (e) {out.req_id}")
        want = golden.get(out.req_id)
        have = (out.result.ii, out.result.n_routing_pes)
        if want is not None and have != want:
            raise AssertionError(f"phase (e) {out.req_id}: (II, routing "
                                 f"PEs) {have} != golden {want}")
    for digest, tracer in tracers.items():
        check_spans(tracer, f"phase (e) {digest[:12]}",
                    interpret=interpret)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the phase (b) random rows")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    log = CompileLog()
    t_all = time.perf_counter()
    dev = run_phase("(a) device", log, phase_device)
    print(f"[tpu] compile cache: {cache_dir}", flush=True)
    run_phase("(b) selection counts", log, phase_counts, args.seed)
    run_phase("(c) conflict build", log, phase_conflict)
    run_phase("(d) device maps", log, phase_maps,
              (("C4K8@8x8", c4k8_8x8), ("loop40@16x16", loop16x16)))
    run_phase("(e) served requests", log, phase_serve)

    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[tpu] total wall {time.perf_counter() - t_all:.3f} s; "
          f"compiles {log.count} ({log.secs:.3f} s, cache hits "
          f"{log.cache_hits}); peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
