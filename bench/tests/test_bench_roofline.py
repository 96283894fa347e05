"""Work counts of the kernels and the peak table."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchkit import roofline  # noqa: E402


def test_selection_counts_at_16x16_size():
    # n_pad 10496 (the 16x16 fabric's 10,464 candidates), W = 328, K 1024
    w = roofline.selection_counts_work(10496, 1024)
    assert 10496 // 32 == 328
    assert w.int_ops == 3 * 1024 * 10496 * 328
    assert w.bytes == 4 * (10496 * 328 + 1024 * 328 + 1024 * 10496)
    least, bound = w.least_s("TPU v5 lite")
    assert bound == "hbm"
    assert least == pytest.approx(w.bytes / 819e9)
    assert 70e-6 < least < 72e-6


def test_conflict_build_counts():
    w = roofline.conflict_build_work(10464)
    assert w.int_ops == roofline.CONFLICT_PAIR_OPS * 10464 ** 2
    assert w.bytes == 4 * (10464 * 8 + 10464 * 327)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.selection_counts_work(256, 8).least_s("cpu")


def test_v5e_peaks_and_source():
    p = roofline.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_s"]) == (
        197e12, 393e12, 819e9)
    assert "TPU v5e" in p["source"]
