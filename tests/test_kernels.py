"""Per-kernel interpret-mode validation against the pure-jnp oracles:
shape/dtype sweeps with assert_allclose (flash attention, SSD scan,
conflict matrix)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd.kernel import ssd_pallas
from repro.kernels.ssd.ref import segsum, ssd_chunked, ssd_step


# ------------------------------------------------------ flash attention
FA_CASES = [
    # b, sq, sk, hq, hkv, d, window, q_offset
    (2, 128, 128, 4, 2, 64, None, 0),       # GQA causal
    (1, 256, 256, 4, 4, 32, None, 0),       # MHA
    (2, 128, 384, 4, 1, 64, None, 256),     # decode-extend vs long cache
    (1, 256, 256, 8, 2, 64, 100, 0),        # sliding window
    (1, 64, 64, 2, 2, 128, 16, 0),          # small window
    (1, 1, 512, 4, 2, 64, None, 511),       # single-token decode
]


# One representative (case, dtype) combination stays in the fast tier-1
# run; the full interpret-mode sweep is `slow` (several minutes of CPU).
def _sweep(cases, fast_idx=(0,)):
    return [c if i in fast_idx else pytest.param(c, marks=pytest.mark.slow)
            for i, c in enumerate(cases)]


@pytest.mark.parametrize("dtype", [jnp.float32,
                                   pytest.param(jnp.bfloat16,
                                                marks=pytest.mark.slow)])
@pytest.mark.parametrize("case", _sweep(FA_CASES))
def test_flash_attention_matches_ref(case, dtype):
    b, sq, sk, hq, hkv, d, win, off = case
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), dtype)
    out = flash_attention_pallas(q, k, v, q_offset=off, window=win,
                                 block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, q_offset=off, window=win)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_ref_matches_dense_sdpa():
    """The chunked online-softmax oracle equals dense masked attention."""
    from repro.models.attention import causal_window_mask, sdpa
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, hq, hkv, d = 2, 96, 4, 2, 32
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    pos = jnp.arange(s)
    mask = causal_window_mask(pos, pos, None)[None, None]
    ref = sdpa(q, k, v, mask)
    out = flash_attention_ref(q, k, v, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ------------------------------------------------------------------ SSD
SSD_CASES = [
    # B, S, H, P, N, chunk, head_block
    (2, 64, 4, 16, 32, 16, 2),
    (1, 128, 8, 32, 64, 32, 4),
    (2, 128, 4, 64, 128, 64, 4),
]


def _ssd_inputs(case, dtype=jnp.float32):
    b, s, h, p, n, chunk, hb = case
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype)
    a_log = (jax.random.normal(ks[2], (h,)) * 0.3).astype(jnp.float32)
    bb = jax.random.normal(ks[3], (b, s, 1, n), dtype)
    cc = jax.random.normal(ks[4], (b, s, 1, n), dtype)
    return x, dt, a_log, bb, cc


@pytest.mark.parametrize("case", _sweep(SSD_CASES))
def test_ssd_pallas_matches_ref(case):
    x, dt, a_log, b, c = _ssd_inputs(case)
    chunk, hb = case[5], case[6]
    y1, f1 = ssd_pallas(x, dt, a_log, b, c, chunk=chunk, head_block=hb,
                        interpret=True)
    y2, f2 = ssd_chunked(x, dt, a_log, b, c, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=1e-4)


@pytest.mark.slow
def test_ssd_chunked_matches_recurrence():
    """Chunked scan == naive token-by-token recurrence, any chunking."""
    x, dt, a_log, b, c = _ssd_inputs((2, 32, 4, 8, 16, 8, 2))
    state = jnp.zeros((2, 4, 8, 16))
    ys = []
    for t in range(32):
        y_t, state = ssd_step(state, x[:, t], dt[:, t], a_log,
                              b[:, t], c[:, t])
        ys.append(y_t)
    y_naive = jnp.stack(ys, axis=1)
    for chunk in (4, 8, 16, 32):
        y_c, fin = ssd_chunked(x, dt, a_log, b, c, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_naive),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(fin), np.asarray(state),
                                   atol=2e-5)


def test_segsum():
    la = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    ss = segsum(la)
    assert float(ss[2, 0]) == pytest.approx(0.5, abs=1e-6)   # 0.2+0.3
    assert float(ss[3, 3]) == pytest.approx(0.0)
    assert np.isneginf(np.asarray(ss)[0, 1])


# ------------------------------------------------------ conflict matrix
def test_conflict_matrix_pallas_sweep():
    from repro.core import make_cnkm, schedule_dfg
    from repro.core.cgra import CGRAConfig
    from repro.core.conflict import build_conflict_graph
    from repro.kernels.conflict_matrix.kernel import conflict_matrix_pallas
    from repro.kernels.conflict_matrix.ref import (conflict_matrix_ref,
                                                   encode)
    for (n, m, blk) in [(2, 4, 32), (2, 6, 64), (4, 4, 128)]:
        sched = schedule_dfg(make_cnkm(n, m), CGRAConfig())
        cg = build_conflict_graph(sched, CGRAConfig())
        feat = encode(cg.vertices)
        ref = conflict_matrix_ref(feat)
        out = np.asarray(conflict_matrix_pallas(
            jnp.asarray(feat), block=blk, interpret=True)).astype(bool)
        assert (out == ref).all()


def test_conflict_matrix_packed_matches_bitset_rows():
    """Packed-word kernel variant: uint32 tiles viewed as uint64 rows
    must equal `pack_bool_rows` of the dense-bool oracle, and the
    `build_conflict_graph(use_kernel="packed")` path must reproduce the
    engine's bitset rows byte-for-byte."""
    import numpy as onp

    from repro.core import make_cnkm, schedule_dfg
    from repro.core.bitset import n_words, pack_bool_rows
    from repro.core.cgra import CGRAConfig
    from repro.core.conflict import build_conflict_graph
    from repro.kernels.conflict_matrix.kernel import \
        conflict_matrix_packed_pallas
    from repro.kernels.conflict_matrix.ops import conflict_matrix_packed
    from repro.kernels.conflict_matrix.ref import (conflict_matrix_ref,
                                                   encode)
    for (n, m, bi, bj) in [(2, 4, 32, 64), (2, 6, 64, 128),
                           (4, 4, 128, 256)]:
        sched = schedule_dfg(make_cnkm(n, m), CGRAConfig())
        cg = build_conflict_graph(sched, CGRAConfig())
        feat = encode(cg.vertices)
        ref_rows = pack_bool_rows(conflict_matrix_ref(feat))
        w32 = onp.ascontiguousarray(onp.asarray(conflict_matrix_packed_pallas(
            jnp.asarray(feat), block_i=bi, block_j=bj, interpret=True)))
        rows = w32.view(onp.uint64)[:, :n_words(len(cg.vertices))]
        assert (rows == ref_rows).all()
        # host path (no pallas) packs the oracle
        assert (conflict_matrix_packed(cg.vertices) == ref_rows).all()



def test_conflict_matrix_packed_default_blocks_span_column_blocks():
    """Default blocks (the ones the TPU compiles): 4200 columns need two
    4096-column word blocks plus a ragged row block, and every padded
    column must stay zero once the words are viewed as uint64 rows."""
    import numpy as onp

    from repro.core.bitset import n_words, pack_bool_rows
    from repro.kernels.conflict_matrix.kernel import \
        conflict_matrix_packed_pallas
    from repro.kernels.conflict_matrix.ref import conflict_matrix_ref
    rng = onp.random.default_rng(7)
    n = 4200
    feat = onp.stack([rng.integers(0, 3, n), rng.integers(0, 300, n),
                      rng.integers(0, 4, n), rng.integers(0, 8, n),
                      rng.integers(0, 8, n), rng.integers(0, 8, n),
                      rng.integers(-1, 2, n), rng.integers(0, 3, n)],
                     axis=1).astype(onp.int32)
    w32 = onp.ascontiguousarray(onp.asarray(
        conflict_matrix_packed_pallas(jnp.asarray(feat), interpret=True)))
    assert w32.shape == (n, 2 * 4096 // 32)
    rows = w32.view(onp.uint64)
    ref = pack_bool_rows(conflict_matrix_ref(feat))
    assert (rows[:, :n_words(n)] == ref).all()
    assert not rows[:, n_words(n):].any()

def test_conflict_matrix_packed_feeds_bitset_graph():
    from repro.core import make_cnkm, schedule_dfg
    from repro.core.cgra import CGRAConfig
    from repro.core.conflict import build_conflict_graph
    sched = schedule_dfg(make_cnkm(2, 6), CGRAConfig())
    ref = build_conflict_graph(sched, CGRAConfig())
    packed = build_conflict_graph(sched, CGRAConfig(), use_kernel="packed")
    assert (packed.bits.rows == ref.bits.rows).all()
    assert packed.n_edges == ref.n_edges


# ---------------------------------------------------- sbts_step counts
@pytest.mark.parametrize("n_pad,k,block_n", [(128, 4, 1024), (384, 16, 256),
                                             (640, 24, 1024)])
def test_selection_counts_pallas_matches_ref(n_pad, k, block_n):
    """The device engine's popcount kernel equals the numpy oracle bit
    for bit, across vertex blocks that divide n_pad (384 -> 128 under a
    256 cap, 640 -> 640) and seed counts block_k does not divide."""
    from repro.kernels.sbts_step.kernel import selection_counts_pallas
    from repro.kernels.sbts_step.ref import selection_counts_ref
    rng = np.random.default_rng(n_pad + k)
    rows = rng.integers(0, 1 << 32, (n_pad, n_pad // 32), dtype=np.uint32)
    sel = rng.integers(0, 1 << 32, (k, n_pad // 32), dtype=np.uint32)
    out = selection_counts_pallas(rows, sel, block_n=block_n,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  selection_counts_ref(rows, sel))
