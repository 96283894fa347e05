"""Public conflict-matrix entrypoint: numpy-vectorised reference by
default (host-side mapping pipeline), Pallas kernel for TPU runs."""

from __future__ import annotations

import sys

import numpy as np

from . import ref


def conflict_matrix(vertices, *, use_pallas: bool = False,
                    interpret: bool = False) -> np.ndarray:
    """core.conflict.Vertex list -> (n, n) bool adjacency of the
    occupancy/clique rules (dense part; dependency edges added by the
    caller)."""
    feat = ref.encode(vertices)
    if use_pallas:
        from . import kernel
        adj = np.asarray(kernel.conflict_matrix_pallas(
            feat, interpret=interpret))
        return adj.astype(bool)
    return ref.conflict_matrix_ref(feat)


def conflict_matrix_packed(vertices, *, use_pallas: bool = False,
                           interpret: bool | None = None) -> np.ndarray:
    """core.conflict.Vertex list -> packed ``uint64 [n, ceil(n/64)]``
    adjacency rows, the layout `core.bitset.BitsetGraph` consumes.

    With ``use_pallas`` the TPU kernel emits uint32 words that are
    reinterpreted pairwise as uint64 on the host (little-endian bit
    order end to end), so the accelerator path feeds the bitset engine
    with no python pack step; the host path packs the dense-bool
    reference — which stays the oracle either way.  ``interpret=None``
    picks the Pallas mode from the backend (`repro.kernels.interpret_mode`)."""
    from repro.core.bitset import n_words, pack_bool_rows

    feat = ref.encode(vertices)
    n = feat.shape[0]
    if not use_pallas:
        return pack_bool_rows(ref.conflict_matrix_ref(feat))
    from . import kernel
    if interpret is None:
        from repro.kernels import interpret_mode
        interpret = interpret_mode()
    w32 = np.asarray(kernel.conflict_matrix_packed_pallas(
        feat, interpret=interpret))
    w32 = np.ascontiguousarray(w32)
    if sys.byteorder == "little":
        rows = w32.view(np.uint64)
    else:  # pragma: no cover - big-endian host
        rows = (w32[:, 0::2].astype(np.uint64)
                | (w32[:, 1::2].astype(np.uint64) << np.uint64(32)))
    return rows[:, :n_words(n)]
