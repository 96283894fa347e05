"""Pallas kernel for the device SBTS step's conflict-count evaluation.

The device portfolio (`repro.core.mis_device.DeviceSBTS`) advances K
tabu trajectories in lock-step; every step needs, for each trajectory
k and each vertex v, the count ``|N(v) ∩ S_k|`` of v's neighbours
inside some packed vertex set S_k (the current selection, the addable
set, the Luby sample).  With the adjacency as packed uint32 words
``rows32 [n_pad, W]`` (`BitsetGraph.rows_u32`) and the selections as
``sel32 [K, W]``, that is one AND + ``lax.population_count`` + word
reduction per (k, v) pair — the all-pairs popcount this kernel tiles
over a (vertex-block, seed-block) grid.

Layout: the wrapper hands the kernel the adjacency transposed,
``[W, n_pad]``, so vertices run along the 128 lanes and the word
reduction runs down the sublanes — plain vector adds, no cross-lane
reduce.  Each grid cell holds one ``(W, block_n)`` adjacency tile
(fetched once per vertex block: the seed axis is the inner grid axis)
and one ``(block_k, W)`` selection tile, and walks the ``block_k``
seeds one at a time, so the live intermediate is a single
``(W, block_n)`` word tile: ~0.3 MiB at the 16x16 fabric's
n_pad 10496, well inside the TPU's scoped VMEM.  ``block_n`` is the
largest multiple of 128 that divides ``n_pad`` and does not exceed the
requested cap (callers pad ``n_pad`` to a multiple of 128,
`mis_device._pad_n`); a ``K`` that ``block_k`` does not divide runs as
one seed block.  `tests/test_tpu_compile.py` compiles this kernel for a
TPU v5e at the 8x8 and 16x16 fabric sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128


def _vertex_block(n_pad: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``n_pad`` and is <= ``cap``
    (``n_pad`` itself when it is not a multiple of 128)."""
    if n_pad % _LANES:
        return n_pad
    b = max(_LANES, min(cap, n_pad) // _LANES * _LANES)
    while n_pad % b:
        b -= _LANES
    return b


def _counts_kernel(rows_t_ref, sel_ref, out_ref):
    rows_t = rows_t_ref[...]                  # (W, block_n) uint32
    sel_t = sel_ref[...].T                    # (W, block_k) uint32
    counts = []
    for kk in range(sel_ref.shape[0]):
        hits = jax.lax.population_count(rows_t & sel_t[:, kk:kk + 1])
        counts.append(hits.astype(jnp.int32).sum(axis=0, keepdims=True))
    out_ref[...] = jnp.concatenate(counts, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def selection_counts_pallas(rows32, sel32, *, block_n: int = 1024,
                            block_k: int = 8,
                            interpret: bool = False):
    """``int32 [K, n_pad]`` of ``popcount(rows32[v] & sel32[k])`` over
    the word axis — |N(v) ∩ S_k| for every (trajectory, vertex) pair.
    ``block_n`` caps the vertex block (see `_vertex_block`)."""
    n_pad, w = rows32.shape
    k, w2 = sel32.shape
    assert w == w2, (rows32.shape, sel32.shape)
    block_n = _vertex_block(n_pad, block_n)
    if k % block_k:
        block_k = k
    grid = (n_pad // block_n, k // block_k)
    return pl.pallas_call(
        _counts_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((w, block_n), lambda i, kk: (0, i)),
                  pl.BlockSpec((block_k, w), lambda i, kk: (kk, 0))],
        out_specs=pl.BlockSpec((block_k, block_n),
                               lambda i, kk: (kk, i)),
        out_shape=jax.ShapeDtypeStruct((k, n_pad), jnp.int32),
        interpret=interpret,
    )(rows32.T, sel32)
