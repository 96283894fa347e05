"""The check catches the control and each planted fault.

The look for a chip is skipped; everything else of a run is driven, at a
size a test run holds: two CnKm requests the program's validator rejects
candidates for, on the 4x4 fabric with the numpy engine."""

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src"), HERE]

from benchkit import faults, harness  # noqa: E402
from checkout import make_checkout, without_chip  # noqa: E402

CYCLE = [{"name": "C5K5", "family": "cnkm", "params": {"n": 5, "m": 5},
          "expect": "binding"},
         {"name": "C2K8.busmap", "family": "cnkm",
          "params": {"n": 2, "m": 8}, "mode": "busmap",
          "expect": "binding"}]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("control"), CYCLE)


def run(root, capsys, monkeypatch):
    without_chip(monkeypatch, root)
    rc = harness.run_cell(root, "tiny.mix", 12345, 0.5, False,
                          t_process=time.perf_counter())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    return res


def test_program_is_correct(root, capsys, monkeypatch):
    res = run(root, capsys, monkeypatch)
    assert res["correct"] is True
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("fault,number", [
    ("validator_off", "violations"),
    ("answer_altered", "violations"),
    ("no_verdict", "no_verdict"),
])
def test_fault_is_caught(root, capsys, monkeypatch, fault, number):
    with faults.FAULTS[fault]():
        res = run(root, capsys, monkeypatch)
    assert res["correct"] is False
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_crash_is_a_failed_request(root, capsys, monkeypatch):
    """A request whose map raises gets no verdict: it counts in
    ``failed`` and the run is not correct, but the run still ends with
    its result line."""
    from repro.core import bandmap

    def crash(*args, **kwargs):
        raise RuntimeError("planted crash")

    monkeypatch.setattr(bandmap, "map_dfg", crash)
    res = run(root, capsys, monkeypatch)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] > 0
