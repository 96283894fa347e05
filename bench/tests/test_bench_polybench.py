"""The polybench4x4.table cell's files: the frozen kernel copy against
the program's lowering, the closed-loop kernel driver, and the readers
of the schedule layer's counts."""

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

from benchkit import harness, polybench  # noqa: E402
from repro.core import kernels_polybench  # noqa: E402

sys.path.insert(0, HERE)
from checkout import make_checkout, without_chip  # noqa: E402

MIX = json.load(open(os.path.join(BENCH, "traffic",
                                  "polybench_table.json")))
DRIVER = harness._module(ROOT, "drivers", "closed_map_kernels")


def frozen_form(g):
    return ([(i, op.kind, op.name, op.latency, op.clone_of)
             for i, op in g.ops.items()], list(g.edges), g.next_id)


def program_form(d):
    return ([(i, op.kind.value, op.name, op.latency, op.clone_of)
             for i, op in d.ops.items()],
            [(e.src, e.dst, e.distance) for e in d.edges], d._next_id)


@pytest.mark.parametrize("unroll", [1, 2, 4])
@pytest.mark.parametrize("kernel", polybench.KERNELS)
def test_frozen_copy_builds_the_programs_graphs(kernel, unroll):
    assert frozen_form(polybench.build(kernel, unroll)) == \
        program_form(kernels_polybench.build(kernel, unroll))


def test_the_mix_is_eight_kernels_two_unrolls_two_modes():
    cycle = MIX["cycle"]
    assert MIX["driver"] == "closed_map_kernels"
    assert len(cycle) == 32 == len({c["name"] for c in cycle})
    assert {(c["kernel"], c["unroll"], c["mode"]) for c in cycle} == {
        (k, u, m) for k in polybench.KERNELS for u in (2, 4)
        for m in ("bandmap", "busmap")}
    assert all(c["expect"] == "binding" for c in cycle)
    DRIVER.check(MIX)


def _two_vio_ops(g):
    vin = {i for i, op in g.ops.items() if op.kind == "vin"}
    preds = {}
    for s, t, _ in g.edges:
        if s in vin:
            preds.setdefault(t, set()).add(s)
    return any(len(p) > 1 for p in preds.values())


def test_most_requests_read_two_memory_operands_in_one_op():
    """28 of the 32 requests have an op with two or more VIO operands;
    the four gemm requests have none."""
    without = sorted(c["name"] for c in MIX["cycle"] if not _two_vio_ops(
        polybench.build(c["kernel"], c["unroll"])))
    assert without == ["gemm.u2.bandmap", "gemm.u2.busmap",
                       "gemm.u4.bandmap", "gemm.u4.busmap"]


@pytest.mark.parametrize("bad", [
    {"order": "random"},
    {"cycle": []},
    {"cycle": [{"name": "x", "kernel": "lu", "unroll": 2}]},
    {"cycle": [{"name": "x", "kernel": "gemm", "unroll": 0}]},
    {"cycle": [{"name": "x", "kernel": "gemm", "unroll": "2"}]},
    {"cycle": [{"name": "x", "kernel": "gemm", "unroll": 2,
                "mode": "fastmap"}]},
    {"cycle": [{"name": "x", "kernel": "gemm", "unroll": 2,
                "expect": "maybe"}]},
    {"cycle": [{"kernel": "gemm", "unroll": 2}]},
])
def test_driver_refuses_a_malformed_mix(bad):
    with pytest.raises((ValueError, KeyError, TypeError)):
        DRIVER.check(dict(MIX, **bad))


def test_seed_only_orders_each_cycle():
    n = len(MIX["cycle"])
    names = [[r.name for _, r in zip(range(2 * n), DRIVER.requests(
        MIX, seed, "bandmap"))] for seed in (5, 5, 2 ** 31 + 6)]
    assert names[0] == names[1] and names[0] != names[2]
    assert sorted(names[0][:n]) == sorted(names[2][n:]) == \
        sorted(c["name"] for c in MIX["cycle"])


def test_driver_refuses_a_program_that_cannot_bind_two_operands(
        monkeypatch):
    """A program answering ``c[i] = a[i] + b[i]`` with no binding makes
    the run fail before its window; this one binds it."""
    from repro.core.cgra import CGRAConfig
    DRIVER.require_two_operand_binding(CGRAConfig(), {"engine": "device"})
    monkeypatch.setattr(DRIVER.program, "map_request",
                        lambda *a, **k: SimpleNamespace(ok=False))
    with pytest.raises(RuntimeError, match="two memory operands"):
        DRIVER.window(MIX, 1, 0.0, CGRAConfig(), {}, False)


# --------------------------------------------------------- readers
def span(name, **counts):
    return SimpleNamespace(name=name, counts=counts)


def run_of(*requests):
    return SimpleNamespace(records=[SimpleNamespace(spans=s)
                                    for s in requests])


def read(name, run):
    return harness.reader(ROOT, name)(run)


def test_readers_of_the_schedule_counts():
    run = run_of(
        [span("map-dfg"),
         span("schedule", **{"schedule.vio_operands": 6,
                             "schedule.staggered": 2,
                             "schedule.hold_cycles": 3})],
        [span("schedule", **{"schedule.vio_operands": 2,
                             "schedule.staggered": 0,
                             "schedule.hold_cycles": 0})],
        None)
    assert read("stagger_share", run) == 25.0
    assert read("stagger_hold_cycles", run) == 1.5


@pytest.mark.parametrize("name", ["stagger_share", "stagger_hold_cycles"])
def test_readers_give_none_without_such_counts(name):
    """A program whose spans count no schedule operands (the parent of
    the staggered scheduler) gives no number, and neither does a run
    with no traced request or no counts at all."""
    assert read(name, run_of([span("map-dfg"), span("schedule")])) is None
    assert read(name, run_of(
        [span("repair", **{"repair.tries": 3})])) is None
    assert read(name, run_of(None, None)) is None
    bare = SimpleNamespace(records=[SimpleNamespace(
        spans=[SimpleNamespace(name="schedule")])])
    assert read(name, bare) is None


def test_hold_cycles_none_when_nothing_staggered():
    run = run_of([span("schedule", **{"schedule.vio_operands": 8,
                                      "schedule.staggered": 0,
                                      "schedule.hold_cycles": 0})])
    assert read("stagger_share", run) == 0.0
    assert read("stagger_hold_cycles", run) is None


# --------------------------------------------- a traced run on the CPU
def test_kernel_driver_runs_a_traced_cell(tmp_path, capsys, monkeypatch):
    """The driver through the harness, numpy engine: every verdict is a
    binding the reference accepts, and the schedule metrics read."""
    cycle = [{"name": "jacobi-1d.u2", "kernel": "jacobi-1d", "unroll": 2,
              "expect": "binding"},
             {"name": "gemm.u2.busmap", "kernel": "gemm", "unroll": 2,
              "mode": "busmap", "expect": "binding"}]
    root = make_checkout(tmp_path, cycle, driver="closed_map_kernels")
    # The readers are linked in with the others; the cell lists them.
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    for m in spec["per_layer"]:
        if m["name"] in ("stagger_share", "stagger_hold_cycles"):
            m["workloads"].append("tiny.mix")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    without_chip(monkeypatch, root)
    rc = harness.run_cell(root, "tiny.mix", 2 ** 31 + 9, 0.3, True,
                          t_process=time.perf_counter())
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True, out.err[-2000:]
    assert res["attempted"] % 2 == 0
    assert res["metrics"]["stagger_share"]["value"] > 0
    assert res["metrics"]["stagger_hold_cycles"]["value"] >= 1
