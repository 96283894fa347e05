"""The harness's arithmetic, its failed rule, its result line, and its
discovery of cells, configurations, mixes and metrics by name."""

import json
import os
import sys
import time
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from benchkit import harness, program, stats, traffic  # noqa: E402

sys.path.insert(0, HERE)
from checkout import make_checkout, without_chip  # noqa: E402


def test_window_rate_uses_the_whole_window():
    assert stats.window_rate(4, 10.0, 30.0) == 0.2
    assert stats.window_rate(0, 10.0, 30.0) is None


def test_p95_counts_every_request_and_misses():
    lat = [float(i) for i in range(1, 101)]
    assert stats.p95_all(lat) == 95.0
    # Misses rank above every latency: five misses put p95 on the
    # slowest answered request, six put it on a miss.
    assert stats.p95_all(lat[:95] + [None] * 5) == 95.0
    assert stats.p95_all(lat[:94] + [None] * 6) is None


def test_ii_efficiency_is_a_ratio_of_sums():
    assert stats.ii_efficiency([(2, 3), (1, 1), (2, 2)]) == 5 / 6
    assert stats.ii_efficiency([]) is None


def test_failed_rule():
    ok = SimpleNamespace(ok=True, proved_infeasible=False)
    proof = SimpleNamespace(ok=False, proved_infeasible=True)
    gave_up = SimpleNamespace(ok=False, proved_infeasible=False)
    assert program.has_verdict(ok) and program.has_verdict(proof)
    assert not program.has_verdict(gave_up)


def test_traffic_order_comes_from_the_seed():
    """The seed shuffles each cycle; every seed asks for the same
    requests, and the same seed for the same order."""
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "paper_table.json")))
    n = len(mix["cycle"])
    names = [[r.name for _, r in zip(range(2 * n), traffic.requests(
        mix, seed, "bandmap"))] for seed in (5, 5, 2 ** 31 + 6)]
    assert names[0] == names[1] and names[0] != names[2]
    assert sorted(names[0][:n]) == sorted(names[2][:n]) == \
        sorted(names[0][n:]) == sorted(c["name"] for c in mix["cycle"])
    fixed = dict(mix, order="fixed")
    assert [r.name for _, r in zip(range(n), traffic.requests(
        fixed, 9, "bandmap"))] == [c["name"] for c in mix["cycle"]]


# ------------------------------------------------- a cell added as files
DUMMY_METRIC = '''"""Requests the window sent (a test metric)."""


def read(run):
    return float(len(run.records))
'''


@pytest.fixture(scope="module")
def dummy_root(tmp_path_factory):
    """A checkout whose benchmark gained a configuration, a mix, a cell
    and a metric by files and entries alone."""
    return make_checkout(
        tmp_path_factory.mktemp("checkout"),
        [{"name": "C2K4", "family": "cnkm", "params": {"n": 2, "m": 4},
          "expect": "binding"},
         {"name": "C4K4.busmap", "family": "cnkm",
          "params": {"n": 4, "m": 4}, "mode": "busmap"}],
        metrics={"requests_sent": (DUMMY_METRIC, {
            "unit": "requests", "better": "higher", "source": "host_clock",
            "layer": "harness", "moves": "verdicts_per_s",
            "workloads": ["tiny.mix"]})})


def run(root, trace, capsys, monkeypatch, **kw):
    without_chip(monkeypatch, root)
    rc = harness.run_cell(root, "tiny.mix", 2 ** 31 + 7, 0.5, trace,
                          t_process=time.perf_counter(), **kw)
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


def test_dummy_cell_result_line(dummy_root, capsys, monkeypatch):
    rc, res, err = run(dummy_root, False, capsys, monkeypatch)
    assert rc == 0
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2 and res["attempted"] % 2 == 0  # cycles
    assert set(res["metrics"]) == {"verdicts_per_s", "ii_efficiency",
                                   "setup_s"}
    assert all(m["unit"] for m in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    last = err.strip().splitlines()[-3:]
    assert [x.split(":")[0] for x in last] == [
        "check violations", "check wrong_verdicts", "check no_verdict"]


def test_dummy_cell_traced_metrics(dummy_root, capsys, monkeypatch):
    rc, res, _ = run(dummy_root, True, capsys, monkeypatch)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["requests_sent"]["value"] == res["attempted"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


DUMMY_DRIVER = '''"""Sends each distinct request once, in reverse (a test driver)."""

import dataclasses
import time

from benchkit import harness, traffic


def check(mix):
    traffic.check_cycle(mix)


def distinct(mix, default_mode):
    return traffic.distinct(mix, default_mode)


def window(mix, seed, seconds, cgra, options, traced):
    reqs = distinct(mix, options.get("mode", "bandmap"))[::-1]
    t0 = time.perf_counter()
    return t0, [harness.send(dataclasses.replace(r, index=i), cgra,
                             options, traced) for i, r in enumerate(reqs)]
'''


def test_driver_added_as_a_file(tmp_path, capsys, monkeypatch):
    """A mix whose driver is a new file under ``bench/drivers/`` runs
    with no edit to the harness: the window is what that driver sent."""
    root = make_checkout(
        tmp_path, [{"name": "C1K2", "family": "cnkm",
                    "params": {"n": 1, "m": 2}},
                   {"name": "C2K4", "family": "cnkm",
                    "params": {"n": 2, "m": 4}}],
        drivers={"reverse_once": DUMMY_DRIVER}, driver="reverse_once")
    rc, res, err = run(root, False, capsys, monkeypatch)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] == 2
    assert "latencies: C2K4=" in err and " C1K2=" in err


def test_mix_naming_no_driver_is_refused(tmp_path):
    root = make_checkout(tmp_path, [{"name": "C1K2", "family": "cnkm",
                                     "params": {"n": 1, "m": 2}}],
                         driver="no_such_driver")
    mix = json.load(open(os.path.join(root, "bench", "traffic",
                                      "mix.json")))
    with pytest.raises(FileNotFoundError):
        harness.load_driver(root, mix)


def test_command_refuses_a_host_without_a_tpu(capsys, monkeypatch):
    """`bench/run.py` on a host whose first device is not a TPU exits
    non-zero and prints no result line."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_run_main", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    rc = mod.main(["--workload", "cnkm4x4.table", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
