"""The reader of the LRF-pressure vertex pairs the program counts on
its ``conflict-build`` spans: the mean of ``conflict.lrf_edges`` per
traced request, and no number where the spans never counted it."""

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

NAME = "lrf_edges_per_request"


def span(name, **counts):
    return SimpleNamespace(name=name, counts=counts)


def run_of(*requests):
    """A run whose records carry the given span lists (None: a request
    of an untraced run)."""
    return SimpleNamespace(records=[SimpleNamespace(spans=s)
                                    for s in requests])


def read(run):
    return harness.reader(ROOT, NAME)(run)


def test_mean_pairs_over_the_traced_requests():
    run = run_of(
        [span("map-dfg"),
         span("conflict-build", **{"conflict.lrf_edges": 288}),
         span("conflict-build"),
         span("conflict-build", **{"conflict.lrf_edges": 32}),
         span("certify", **{"certify.csp_nodes": 977})],
        [span("map-dfg"), span("conflict-build")],
        [span("map-dfg")],
        None)                               # not traced: not counted
    assert read(run) == 320 / 3


def test_none_where_no_span_counted_pairs():
    """Spans that carry counts but no ``conflict.lrf_edges`` (a program
    without the pass, or a run whose graphs gained no pair), spans
    without counts, and a run with nothing traced give no number and
    raise nothing."""
    counted = run_of(
        [span("conflict-build"),
         span("certify", **{"certify.csp_nodes": 113})],
        [span("repair", **{"repair.tries": 2, "repair.nodes": 40})])
    old = SimpleNamespace(name="conflict-build", attrs={"n_edges": 12})
    for run in (counted, run_of([old, old]), run_of(None, None),
                run_of([]), run_of()):
        assert read(run) is None


def test_reader_reports_in_a_traced_cell_run(tmp_path, capsys,
                                             monkeypatch):
    """A traced run of a cell that lists the metric reports it from the
    program's own spans: every map of loop4x4s124 builds the same three
    graphs, and the one at II 2 jitter 3 gains the same 320 pairs."""
    root = make_checkout(tmp_path, [{
        "name": "loop4x4s124", "family": "loop",
        "params": {"n_chains": 2, "chain_len": 4, "n_inputs": 3,
                   "n_outputs": 2, "n_carries": 1, "max_distance": 2,
                   "seed": 124},
        "mode": "bandmap", "expect": "binding"}])
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    for m in spec["per_layer"]:
        if m["name"] == NAME:
            m["workloads"] = m["workloads"] + ["tiny.mix"]
    with open(path, "w") as f:
        json.dump(spec, f)
    without_chip(monkeypatch, root)
    rc = harness.run_cell(root, "tiny.mix", 2 ** 31 + 23, 0.2, True,
                          t_process=time.perf_counter())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "pairs"
    assert got["value"] == 320
