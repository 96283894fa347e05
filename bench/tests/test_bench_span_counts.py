"""The readers of the counts the program's spans carry: repair tries per
request, the share of tries that close the shortfall, and the share of
complete candidates the validator rejects; on synthetic runs, on spans
that carry no counts, and in a traced run of a cell."""

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

from benchkit import harness  # noqa: E402

sys.path.insert(0, HERE)
from checkout import make_checkout, without_chip  # noqa: E402

NEW = ("repair_tries_per_request", "repair_fix_share",
       "validate_reject_share")


def span(name, **counts):
    return SimpleNamespace(name=name, counts=counts)


def run_of(*requests):
    """A run whose records carry the given span lists (None: a request
    of an untraced run)."""
    return SimpleNamespace(records=[SimpleNamespace(spans=s)
                                    for s in requests])


def read(name, run):
    return harness.reader(ROOT, name)(run)


def test_readers_sum_counts_over_the_traced_requests():
    run = run_of(
        [span("map-dfg"),
         span("repair", **{"repair.tries": 6}),
         span("repair", **{"repair.tries": 2, "repair.fixed": 1}),
         span("validate", **{"validate.calls": 1, "validate.rejects": 1}),
         span("validate", **{"validate.calls": 1})],
        [span("map-dfg"),
         span("validate", **{"validate.calls": 1, "validate.rejects": 1}),
         span("validate", **{"validate.calls": 1})],
        None)                               # not traced: not counted
    assert read("repair_tries_per_request", run) == 4.0
    assert read("repair_fix_share", run) == pytest.approx(12.5)
    assert read("validate_reject_share", run) == 50.0


def test_shares_are_none_without_a_try_or_a_call():
    run = run_of([span("map-dfg"), span("certify")])
    assert read("repair_tries_per_request", run) == 0.0
    assert read("repair_fix_share", run) is None
    assert read("validate_reject_share", run) is None


def test_spans_without_counts_give_no_number():
    """A program whose spans carry no counts, or a run with nothing
    traced, reports none of the three and raises nothing."""
    old = SimpleNamespace(name="repair", attrs={})
    for run in (run_of([old, old]), run_of(None, None), run_of([]),
                run_of()):
        assert [read(m, run) for m in NEW] == [None, None, None]


def test_readers_report_in_a_traced_cell_run(tmp_path, capsys,
                                             monkeypatch):
    """A traced run of a cell that lists the three metrics reports them
    from the program's own spans: C5K5's validator turns down four
    complete candidates before it keeps one; no repair runs."""
    root = make_checkout(tmp_path, [{"name": "C5K5", "family": "cnkm",
                                     "params": {"n": 5, "m": 5},
                                     "expect": "binding"}])
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    for m in spec["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = m["workloads"] + ["tiny.mix"]
    with open(path, "w") as f:
        json.dump(spec, f)
    without_chip(monkeypatch, root)
    rc = harness.run_cell(root, "tiny.mix", 2 ** 31 + 11, 0.5, True,
                          t_process=time.perf_counter())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    got = res["metrics"]
    assert got["repair_tries_per_request"] == {"value": 0.0,
                                               "unit": "tries"}
    assert "repair_fix_share" not in got
    assert got["validate_reject_share"] == {"value": 80.0, "unit": "%"}
