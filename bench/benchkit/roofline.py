"""Peaks of the chips the benchmark runs on, and the work of each kernel.

The count functions count what the algorithm has to do, from the shapes
alone: every operand read once, every result written once, and the
operations the definition needs.  A kernel that reads an operand twice,
or does work the definition does not ask for, gets no credit for it.

`PEAKS` is keyed by ``device_kind`` as JAX reports it; a kind that is
not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
# The page gives no rate for the vector unit's integer operations.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table for device kind {device_kind!r}")
    return PEAKS[device_kind]


@dataclasses.dataclass(frozen=True)
class Work:
    int_ops: int        # vector-unit integer operations
    bytes: int          # HBM bytes read and written

    def least_s(self, device_kind: str) -> tuple[float, str]:
        """The least time the chip could take and the bound that sets
        it.  Only the memory bound has a published peak for this work
        (it runs on the vector unit), so the memory bound is the one
        applied."""
        return self.bytes / peaks(device_kind)["hbm_bytes_s"], "hbm"


def selection_counts_work(n_pad: int, k: int) -> Work:
    """``counts[k, v] = popcount(rows[v] & sel[k])`` summed over the
    ``W = n_pad / 32`` uint32 words: an AND, a popcount and an add per
    (trajectory, vertex, word); the adjacency ``[n_pad, W]`` and the
    selections ``[K, W]`` read once, the int32 counts ``[K, n_pad]``
    written once."""
    w = n_pad // 32
    return Work(int_ops=3 * k * n_pad * w,
                bytes=4 * (n_pad * w + k * w + k * n_pad))


# Per vertex pair, the packed conflict build evaluates: same op (1
# compare), input-port clash (two kind tests, port and slot compares,
# three ANDs), output-port clash (the same, 7), PE clash (kind tests,
# row, column and slot compares, four ANDs: 9), and three ORs to
# combine them: 1 + 7 + 7 + 9 + 3 = 27 operations, then one bit packed.
CONFLICT_PAIR_OPS = 27
CONFLICT_FEATURES = 8


def conflict_build_work(n: int) -> Work:
    """The packed conflict matrix of ``n`` candidate vertices: the
    occupancy and same-op rules over all ``n * n`` pairs, the int32
    feature rows ``[n, 8]`` read once and the packed bit rows
    ``[n, ceil(n / 32)]`` of uint32 written once."""
    words = -(-n // 32)
    return Work(int_ops=CONFLICT_PAIR_OPS * n * n,
                bytes=4 * (n * CONFLICT_FEATURES + n * words))
