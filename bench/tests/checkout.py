"""A throwaway checkout whose benchmark gained a configuration, a mix and
a cell by files and entries alone, and the stand-in for the look for a
chip (shared by the harness and control tests)."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY = {"name": "tiny", "fabric": {"rows": 4, "cols": 4, "lrf": 8,
                                   "grf": 0, "buses_per_scope": 2},
        "options": {"engine": "numpy"}}


def make_checkout(root, cycle, *, metrics=None, drivers=None,
                  driver="closed_map_dfg"):
    """Build the checkout under ``root`` with one cell ``tiny.mix``
    mapping ``cycle`` on a 4x4 fabric with the numpy engine, its
    requests sent by ``driver``; ``metrics`` maps extra metric names to
    (reader source, BENCHMARK.json entry), ``drivers`` extra driver
    names to their source."""
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    bench = root / "bench"
    for sub in ("configs", "traffic", "metrics", "drivers"):
        (bench / sub).mkdir(parents=True)
    os.symlink(os.path.join(BENCH, "benchkit"), bench / "benchkit")
    for sub in ("metrics", "drivers"):
        for f in os.listdir(os.path.join(BENCH, sub)):
            if f.endswith(".py"):
                os.symlink(os.path.join(BENCH, sub, f), bench / sub / f)
    for name, source in (drivers or {}).items():
        (bench / "drivers" / f"{name}.py").write_text(source)
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "mix.json").write_text(json.dumps({
        "driver": driver, "order": "shuffle", "cycle": cycle}))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.mix", "config": "tiny",
                              "traffic": "mix", "chips": 1, "why": "test"})
    for name, (source, entry) in (metrics or {}).items():
        (bench / "metrics" / f"{name}.py").write_text(source)
        spec["per_layer"].append(dict(entry, name=name))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def without_chip(monkeypatch, root):
    """Skip the look for a chip: the run takes the host's first device
    as its own.  The compile cache's variable is restored afterwards."""
    from benchkit import harness

    def host_device(chips):
        import jax
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}

    monkeypatch.setattr(harness, "device_info", host_device)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(root, ".jax_cache"))
