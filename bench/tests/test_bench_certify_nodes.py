"""The reader of the exact-search nodes the program counts on its
``certify`` spans: total ``certify.csp_nodes`` over the traced requests,
and no number where the spans never counted it."""

import json
import os
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from benchkit import harness  # noqa: E402

sys.path.insert(0, HERE)
from checkout import make_checkout, without_chip  # noqa: E402

NAME = "certify_nodes_per_request"


def span(name, **counts):
    return SimpleNamespace(name=name, counts=counts)


def run_of(*requests):
    """A run whose records carry the given span lists (None: a request
    of an untraced run)."""
    return SimpleNamespace(records=[SimpleNamespace(spans=s)
                                    for s in requests])


def read(run):
    return harness.reader(ROOT, NAME)(run)


def test_total_nodes_over_the_traced_requests():
    run = run_of(
        [span("map-dfg"),
         span("certify", **{"certify.csp_nodes": 4206,
                            "certify.orbit_skips": 19}),
         span("certify", **{"certify.csp_nodes": 898,
                            "certify.orbit_skips": 0}),
         span("repair", **{"repair.tries": 1, "repair.nodes": 3})],
        [span("map-dfg"), span("certify")],
        [span("map-dfg")],
        None)                               # not traced: not counted
    assert read(run) == 5104 / 3


def test_none_where_no_span_counted_nodes():
    """Spans that carry counts but no ``certify.csp_nodes`` (a program
    that does not count them), spans without counts, and a run with
    nothing traced give no number and raise nothing."""
    counted = run_of(
        [span("repair", **{"repair.tries": 6, "repair.nodes": 700}),
         span("validate", **{"validate.calls": 1})],
        [span("certify")])
    old = SimpleNamespace(name="certify", attrs={"nodes": 12})
    for run in (counted, run_of([old, old]), run_of(None, None),
                run_of([]), run_of()):
        assert read(run) is None


def test_reader_reports_in_a_traced_cell_run(tmp_path, capsys,
                                             monkeypatch):
    """A traced run of a cell that lists the metric reports it from the
    program's own spans: every map of loop4x4s108 runs the same exact
    searches, so every request adds the same nodes."""
    root = make_checkout(tmp_path, [{
        "name": "loop4x4s108", "family": "loop",
        "params": {"n_chains": 2, "chain_len": 4, "n_inputs": 3,
                   "n_outputs": 2, "n_carries": 0, "max_distance": 2,
                   "seed": 108},
        "mode": "bandmap", "expect": "binding"}])
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    for m in spec["per_layer"]:
        if m["name"] == NAME:
            m["workloads"] = m["workloads"] + ["tiny.mix"]
    with open(path, "w") as f:
        json.dump(spec, f)
    without_chip(monkeypatch, root)
    rc = harness.run_cell(root, "tiny.mix", 2 ** 31 + 17, 0.2, True,
                          t_process=time.perf_counter())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "nodes"
    assert got["value"] > 0 and got["value"] == int(got["value"])
