"""Pallas TPU kernel for tiled conflict-matrix construction.

Grid (nI, nJ) over (block × block) tiles of the n×n adjacency.  Each
program loads two (block, 8) int32 feature tiles into VMEM and evaluates
the occupancy/clique predicate with broadcast compares on the VPU —
8-lane int32 compares over a 256×256 tile are ~0.5 MiB of VMEM traffic
and no MXU work, so the kernel is VPU/bandwidth-bound; block=256 keeps
three tiles (two features + one output) < 1 MiB VMEM.

Output int8 (bool-like); the host MIS solver consumes it directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import N_FEATURES, QUAD, TIN, TOUT


def _cm_kernel(fi_ref, fj_ref, o_ref, *, block: int, n: int):
    bi = pl.program_id(0)
    bj = pl.program_id(1)
    fi = fi_ref[...]                       # (block, 8)
    fj = fj_ref[...]

    def col(ref, k):
        return ref[:, k]

    ki, oi, mi, pi = col(fi, 0), col(fi, 1), col(fi, 2), col(fi, 3)
    ri, ci = col(fi, 4), col(fi, 5)
    kj, oj, mj, pj = col(fj, 0), col(fj, 1), col(fj, 2), col(fj, 3)
    rj, cj = col(fj, 4), col(fj, 5)

    def outer_eq(a, b):
        return a[:, None] == b[None, :]

    same_op = outer_eq(oi, oj)
    same_m = outer_eq(mi, mj)
    same_port = outer_eq(pi, pj)
    same_pe = outer_eq(ri, rj) & outer_eq(ci, cj)

    def both(k):
        return (ki[:, None] == k) & (kj[None, :] == k)

    adj = same_op
    adj |= both(TIN) & same_port & same_m
    adj |= both(TOUT) & same_port & same_m
    adj |= both(QUAD) & same_pe & same_m

    # mask diagonal and padding
    gi = bi * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    gj = bj * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    adj &= gi != gj
    adj &= (gi < n) & (gj < n)
    o_ref[...] = adj.astype(jnp.int8)


def _cm_packed_kernel(fi_ref, fj_ref, o_ref, *, block_i: int, n: int):
    """Packed variant: emit the (block_i, 32 * w) adjacency tile as
    ``(block_i, w)`` int32 words, bit ``b`` of word ``c`` being column
    ``32 * c + b`` (little-endian within the word, matching
    bitset.pack_bool's layout once word pairs are viewed as uint64).
    ``fj_ref`` holds the column features bit-major, ``(32, 8, w)``: its
    slice ``b`` is the features of columns ``32 * c + b`` for every word
    ``c`` of the tile, laid along the lanes, so each of the 32 bit
    planes is one lane-dense compare over a ``(block_i, w)`` tile — no
    reshape and no reduction.  Words are packed in int32 (bit 31 is the
    sign bit) and bitcast to uint32 by the caller."""
    bi = pl.program_id(0)
    bj = pl.program_id(1)
    w = o_ref.shape[1]
    fi = fi_ref[...]                       # (block_i, 8)
    ki, oi, mi, pi, ri, ci = (fi[:, c:c + 1] for c in range(6))
    gi = bi * block_i + jax.lax.broadcasted_iota(jnp.int32, (block_i, w), 0)
    col0 = 32 * (bj * w + jax.lax.broadcasted_iota(
        jnp.int32, (block_i, w), 1))
    keep_i = gi < n

    def bit_plane(b, word):
        fj = fj_ref[b]                     # (8, w)
        kj, oj, mj, pj, rj, cj = (fj[c:c + 1, :] for c in range(6))
        same_m = mi == mj
        same_port = pi == pj
        adj = oi == oj
        adj |= (ki == TIN) & (kj == TIN) & same_port & same_m
        adj |= (ki == TOUT) & (kj == TOUT) & same_port & same_m
        adj |= (ki == QUAD) & (kj == QUAD) & (ri == rj) & (ci == cj) \
            & same_m
        gj = col0 + b
        adj &= (gi != gj) & keep_i & (gj < n)
        return word | (adj.astype(jnp.int32) << b)

    o_ref[...] = jax.lax.fori_loop(
        0, 32, bit_plane, jnp.zeros((block_i, w), jnp.int32))


@functools.partial(jax.jit, static_argnames=("block_i", "block_j",
                                             "interpret"))
def conflict_matrix_packed_pallas(feat, *, block_i: int = 256,
                                  block_j: int = 4096,
                                  interpret: bool = False):
    """feat: (n, 8) int32 -> (n, ceil(n/block_j)*block_j/32) uint32
    packed adjacency words.  ``block_j`` must be a multiple of 64 so
    the host can reinterpret word pairs as uint64 rows.  Its default
    (4096 columns -> 128 uint32 lanes) makes each output tile exactly
    one lane row wide; a graph narrower than ``block_j`` runs as one
    column block spanning the whole (64-padded) width, which the TPU
    tiling rules also accept."""
    assert block_j % 64 == 0
    n = feat.shape[0]
    block_j = min(block_j, max(64, -(-n // 64) * 64))
    npad_i = -(-n // block_i) * block_i
    npad_j = -(-n // block_j) * block_j
    fp_i = jnp.pad(feat, ((0, npad_i - n), (0, 0)), constant_values=-7)
    fp_j = jnp.pad(feat, ((0, npad_j - n), (0, 0)), constant_values=-7)
    # Bit-major column features: fj[b, f, c] = feature f of column
    # 32 * c + b.
    fj = fp_j.reshape(npad_j // 32, 32, N_FEATURES).transpose(1, 2, 0)
    w = block_j // 32

    words = pl.pallas_call(
        functools.partial(_cm_packed_kernel, block_i=block_i, n=n),
        grid=(npad_i // block_i, npad_j // block_j),
        in_specs=[
            pl.BlockSpec((block_i, N_FEATURES), lambda i, j: (i, 0)),
            pl.BlockSpec((32, N_FEATURES, w), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_i, w), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad_i, npad_j // 32),
                                       jnp.int32),
        interpret=interpret,
    )(fp_i, fj)
    return jax.lax.bitcast_convert_type(words[:n], jnp.uint32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def conflict_matrix_pallas(feat, *, block: int = 256,
                           interpret: bool = False):
    """feat: (n, 8) int32 -> (n, n) int8 adjacency."""
    n = feat.shape[0]
    npad = -(-n // block) * block
    fp = jnp.pad(feat, ((0, npad - n), (0, 0)), constant_values=-7)
    nb = npad // block

    out = pl.pallas_call(
        functools.partial(_cm_kernel, block=block, n=n),
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((block, N_FEATURES), lambda i, j: (i, 0)),
            pl.BlockSpec((block, N_FEATURES), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, npad), jnp.int8),
        interpret=interpret,
    )(fp, fp)
    return out[:n, :n]
