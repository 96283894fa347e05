"""Trace reduction: busy union, idle gaps named by annotations, kernel
time by name, on synthetic events and on a trace recorded on a TPU."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchkit import xtrace  # noqa: E402

FIXTURE = os.path.join(HERE, "data", "tpu_small.xplane.pb")


def synthetic():
    dev = [("fusion.1", 1.0, 2.0), ("counts_kernel", 1.5, 3.0),
           ("counts_kernel", 6.0, 6.5), ("copy", 9.0, 9.5)]
    host = [("window", 0.0, 10.0), ("request 0 a", 0.5, 4.0),
            ("request 1 b", 4.0, 8.0), ("b repair", 4.2, 5.8)]
    return xtrace.Trace({"/device:TPU:0": dev}, host)


def test_busy_union_and_window():
    tr = synthetic()
    win = tr.window()
    assert win == (0.0, 10.0)
    assert tr.busy_s(win) == pytest.approx(2.0 + 0.5 + 0.5)


def test_idle_gaps_named_by_innermost_annotation():
    tr = synthetic()
    gaps = tr.idle_gaps(tr.window())
    assert gaps[0] == ["b repair", pytest.approx(3.0)]      # 3.0 .. 6.0
    assert ["request 0 a", pytest.approx(1.0)] in gaps       # 0.0 .. 1.0
    assert ["outside requests", pytest.approx(0.5)] in gaps  # 9.5 .. 10
    assert sum(g[1] for g in gaps) == pytest.approx(10.0 - 3.0)


def test_kernel_time_and_top_ops():
    tr = synthetic()
    assert sorted(d for _, d in tr.kernel_events(
        "counts_kernel", tr.window())) == [0.5, 1.5]
    top = tr.top_ops(tr.window())
    assert top[0] == ["counts_kernel", 2.0]


def test_no_device_no_busy():
    tr = xtrace.Trace({}, [("window", 0.0, 1.0)])
    assert tr.busy_s(tr.window()) == 0.0 and tr.idle_gaps((0, 1)) == []


def test_recorded_tpu_trace():
    tr = xtrace.read_trace(FIXTURE)
    assert list(tr.devices) == ["/device:TPU:0"]
    win = tr.window()
    assert 0 < tr.busy_s(win) < win[1] - win[0]
    names = {n for n, _, _ in tr.host}
    assert {"window", "request 0 small", "request 1 small"} <= names
    calls = tr.kernel_events(r"^selection_counts_pallas$", win)
    # 3 calls per lock-step iteration, 192 iterations, K 64, n_pad 256
    assert len(calls) == 3 * 192
    assert all("= s32[64,256]" in text for text, _ in calls)
    gaps = tr.idle_gaps(win)
    assert gaps and all(g[1] > 0 for g in gaps)


def test_roofline_reader_on_recorded_trace():
    """The count kernel's share of its HBM bound reads between 0 and 100%
    on the recorded calls (K 64, n_pad 256)."""
    import importlib.util
    from types import SimpleNamespace
    path = os.path.join(os.path.dirname(HERE), "metrics",
                        "sbts_counts_roofline.py")
    spec = importlib.util.spec_from_file_location("roofline_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = SimpleNamespace(trace=xtrace.read_trace(FIXTURE),
                          device_kind="TPU v5 lite")
    share = mod.read(run)
    assert 0 < share < 100
    assert mod.read(SimpleNamespace(trace=None)) is None
