"""Runs one cell once: set-up, the measured window, the check, the result.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic mix
``bench/traffic/<traffic>.json``, the driver the mix names,
``bench/drivers/<driver>.py``, and one reader per metric,
``bench/metrics/<metric>.py`` with a ``read(run)`` function that returns
a number or None.  Adding a cell, a configuration, a mix, a driver or a
metric is adding files and entries; nothing here names one.

A driver module has three functions:

``check(mix)``
    raises ``ValueError`` for a mix it cannot honour;
``distinct(mix, default_mode)``
    every distinct `traffic.Request` the mix can send (the warm-up
    compiles the device programs these can reach);
``window(mix, seed, seconds, cgra, options, traced)``
    sends the requests for ``seconds`` and returns the window's start
    on ``time.perf_counter()`` and its `Record` list, one per request
    (`send` makes one for a request through `map_dfg`).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback

from . import program, reference, xtrace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_DIR = ".bench_trace"
CACHE_DIR = ".jax_cache"


@dataclasses.dataclass
class Record:
    """One request of the window."""
    index: int
    name: str
    expect: str
    graph: object
    t_submit: float
    t_done: float = 0.0
    result: object = None        # the program's answer, as returned
    verdict: reference.Verdict | None = None
    answered: bool = False
    spans: list | None = None
    span_offset: float = 0.0     # tracer epoch - annotation start (s)

    @property
    def latency_s(self) -> float | None:
        return self.t_done - self.t_submit if self.answered else None


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cell: dict
    config: dict
    setup_s: float
    t_start: float
    t_last: float
    records: list
    device_kind: str
    compiles_in_window: int
    trace: xtrace.Trace | None = None


class CompileLog:
    """Counts XLA compiles, persistent-cache loads included, and the
    loads, through JAX's monitoring hooks."""

    def __init__(self) -> None:
        import jax
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


# ----------------------------------------------------------- discovery
def load_json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def find_cell(spec: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def applies(metric: dict, cell: dict, reported: set[str]) -> bool:
    """Whether ``metric`` is reported in ``cell``: the cells its
    ``workloads`` key lists, or, without the key, every cell that
    reports the end-to-end metric it moves (or every cell, for an
    end-to-end metric)."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def cell_metrics(spec: dict, cell: dict, trace: bool) -> list[dict]:
    e2e = [m for m in spec["end_to_end"] if applies(m, cell, set())]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"] if applies(m, cell, names)]


def _module(root: str, sub: str, name: str):
    path = os.path.join(root, "bench", sub, f"{name}.py")
    tag = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"bench_{sub}_{tag}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root: str, name: str):
    return _module(root, "metrics", name).read


def load_driver(root: str, mix: dict):
    """The module that sends a mix's requests, checked against it."""
    drv = _module(root, "drivers", mix["driver"])
    drv.check(mix)
    return drv


# ------------------------------------------------------------ the run
def device_info(chips: int) -> dict | None:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform {info['platform']}, kind {info['kind']!r}, "
          f"count {info['count']}", file=sys.stderr, flush=True)
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"this cell needs {chips} TPU chip(s); found "
              f"{info['count']} {info['platform']} device(s)",
              file=sys.stderr, flush=True)
        return None
    return info


def warm_up(reqs: list, cgra, options: dict) -> None:
    """Compile the device engine's programs at every graph size the
    requests' schedules can reach; map nothing."""
    if options.get("engine") != "device":
        return
    shapes = set()
    for r in reqs:
        shapes |= program.device_shapes(r.graph, cgra,
                                        {**options, "mode": r.mode})
    for n_pad in sorted(shapes):
        program.warm_device(n_pad, options)


def send(req, cgra, options: dict, traced: bool) -> Record:
    """One request through `map_dfg`, timed from its submission, inside
    a profiler annotation that names it; traced, with the program's
    spans."""
    import jax
    rec = Record(req.index, req.name, req.expect, req.graph,
                 t_submit=time.perf_counter())
    with jax.profiler.TraceAnnotation(
            f"{xtrace.PREFIX}request {req.index} {req.name}"):
        t_ann = time.perf_counter()
        tracer = None
        if traced:
            from repro.obs.trace import Tracer
            tracer = Tracer()
        try:
            res = program.map_request(req.graph, cgra,
                                      {**options, "mode": req.mode},
                                      tracer=tracer)
            rec.result = res
            rec.answered = program.has_verdict(res)
        except Exception:          # no verdict: counted as failed
            traceback.print_exc(limit=4, file=sys.stderr)
    rec.t_done = time.perf_counter()
    if tracer is not None:
        rec.spans = tracer.finished
        rec.span_offset = tracer.epoch - t_ann
    return rec


def check(records: list[Record], fab, max_ii: int) -> dict:
    """Replay every verdict of the window on the reference."""
    violations = wrong = missing = 0
    for rec in records:
        if not rec.answered:
            missing += 1
            continue
        try:
            bad = reference.check_verdict(rec.graph, fab, max_ii,
                                          rec.verdict)
        except Exception as exc:       # a verdict too malformed to replay
            bad = [f"replay failed: {exc!r}"]
        if bad:
            violations += len(bad)
            print(f"request {rec.index} {rec.name}: " + "; ".join(bad[:3]),
                  file=sys.stderr)
        if rec.expect == "binding" and not rec.verdict.ok:
            wrong += 1
            print(f"request {rec.index} {rec.name}: proved unbindable, "
                  f"but the kernel is known to bind", file=sys.stderr)
    return {"violations": {"value": violations, "limit": 0},
            "wrong_verdicts": {"value": wrong, "limit": 0},
            "no_verdict": {"value": missing, "limit": 0}}


def attach_spans(tr: xtrace.Trace, records: list[Record]) -> None:
    """Put the program's spans on the trace's clock, so idle gaps are
    named by what the host was doing inside a request."""
    starts = {n: a for n, a, _ in tr.host}
    for rec in records:
        a = starts.get(f"request {rec.index} {rec.name}")
        if a is None or not rec.spans:
            continue
        base = a + rec.span_offset
        for sp in rec.spans:
            tr.host.append((f"{rec.name} {sp.name}", base + sp.t0,
                            base + sp.t1))


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_process: float, keep_trace: str | None = None,
             out=None) -> int:
    """One run of one cell; prints the result line and returns the exit
    code."""
    out = out or sys.stdout
    spec = load_json(root, "BENCHMARK.json")
    cell, cfg_entry = find_cell(spec, workload)
    config = load_json(root, cfg_entry["file"])
    mix = load_json(root, os.path.join("bench", "traffic",
                                       f"{cell['traffic']}.json"))
    drv = load_driver(root, mix)
    metrics = cell_metrics(spec, cell, trace)
    readers = {m["name"]: reader(root, m["name"]) for m in metrics}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, CACHE_DIR)
    dev = device_info(int(cell["chips"]))
    if dev is None:
        return 3
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    log = CompileLog()
    fab = reference.Fabric(**config["fabric"])
    cgra = program.program_fabric(fab)
    options = dict(config["options"])
    max_ii = int(options.get("max_ii", 32))

    t_warm = time.perf_counter()
    warm_up(drv.distinct(mix, options.get("mode", "bandmap")), cgra,
            options)
    setup_s = time.perf_counter() - t_process
    print(f"set-up: {t_warm - t_process:.3f} s to the warm-up (imports, "
          f"device start), warm-up {time.perf_counter() - t_warm:.3f} s, "
          f"{log.count} compiles ({log.cache_hits} from the cache)",
          file=sys.stderr, flush=True)
    compiles0 = log.count

    trace_dir = os.path.join(root, TRACE_DIR, workload)
    if trace:
        import jax
        shutil.rmtree(trace_dir, ignore_errors=True)
        # Device operations and the benchmark's own annotations only: the
        # Python tracer would record every call of the mapper and slow
        # it several-fold.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(f"{xtrace.PREFIX}window")
        ann.__enter__()
    try:
        t0, records = drv.window(mix, seed, seconds, cgra, options, trace)
    finally:
        if trace:
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
    compiles = log.count - compiles0
    t_last = max((r.t_done for r in records if r.answered), default=t0)
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    run = Run(cell, config, setup_s, t0, t_last, records, dev["kind"],
              compiles)
    if trace:
        path = xtrace.latest_xplane(trace_dir)
        run.trace = xtrace.read_trace(path) if path else None
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    for rec in records:
        if rec.result is not None:
            rec.verdict = program.verdict(rec.result)
    checks = check(records, fab, max_ii)

    values = {}
    for m in metrics:
        v = readers[m["name"]](run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {
        "correct": bool(records) and all(c["value"] <= c["limit"]
                                         for c in checks.values()),
        "attempted": len(records),
        "failed": checks["no_verdict"]["value"],
        "metrics": values,
        "device": dev,
    }
    if trace and run.trace is not None:
        tr = run.trace
        attach_spans(tr, records)
        win = tr.window()
        dev["busy_s"] = tr.busy_s(win)
        dev["window_s"] = win[1] - win[0]
        result["breakdown"] = {"device_ops": tr.top_ops(win),
                               "idle_gaps": tr.idle_gaps(win)}
    result["checks"] = checks
    print(f"window: {len(records)} requests, "
          f"{sum(r.answered for r in records)} verdicts, "
          f"{t_last - t0:.3f} s, compiles inside {compiles}, "
          f"set-up {setup_s:.3f} s", file=sys.stderr)
    print("latencies: " + " ".join(
        f"{r.name}={r.latency_s:.3f}" if r.answered else f"{r.name}=none"
        for r in records), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0
