"""Per-kernel interpret-mode validation against the pure-jnp oracles,
swept over shapes and dtypes (per the deliverable-(c) requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal env: property tests skip, rest run
    from _hypothesis_stub import given, settings, st

from repro.core import cost_model
from repro.core.tiling import Tile
from repro.kernels.attention import decode_ref, gqa_decode_attention, \
    mha_attention
from repro.kernels.attention import kernel as attn_kernel
from repro.kernels.attention.ref import attention_ref
from repro.kernels.matmul import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.spmv import pack_csr, spmv
from repro.kernels.spmv.ref import spmv_ell_ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k", [
    (128, 128, 128), (64, 64, 64), (130, 70, 50), (256, 384, 512),
    (8, 8, 8), (1, 128, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_matches_oracle(m, n, k, dtype):
    a = jax.random.normal(KEY, (m, k), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
    out = matmul(a, b, tile=Tile(64, 64, 64), interpret=True)
    ref = matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("tile", [Tile(32, 32, 32), Tile(64, 32, 96),
                                  Tile(16, 64, 32)])
def test_matmul_kernel_tile_sweep(tile):
    a = jax.random.normal(KEY, (96, 96), jnp.float32)
    b = jax.random.normal(KEY, (96, 96), jnp.float32)
    out = matmul(a, b, tile=tile, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# spmv
# ---------------------------------------------------------------------------

def _random_csr(rng, m, n, density):
    dense = (rng.random((m, n)) < density) * rng.standard_normal((m, n))
    nnz_per_row = (dense != 0).sum(1)
    indptr = np.concatenate([[0], np.cumsum(nnz_per_row)]).astype(np.int32)
    cols = (np.concatenate([np.nonzero(r)[0] for r in dense])
            .astype(np.int32) if nnz_per_row.sum() else
            np.zeros(0, np.int32))
    vals = dense[dense != 0].astype(np.float32)
    return dense, indptr, cols, vals


@pytest.mark.parametrize("m,n,density", [
    (555, 300, 0.02),     # Maragal_2-like skew
    (91, 91, 0.5),        # BIBD-like dense-ish
    (2030, 128, 0.05),    # LD_pilot87-like rows
])
@pytest.mark.parametrize("scheme", ["round_robin", "lpt", "none"])
def test_spmv_kernel_matches_dense(m, n, density, scheme):
    rng = np.random.default_rng(m + n)
    dense, indptr, cols, vals = _random_csr(rng, m, n, density)
    x = rng.standard_normal(n).astype(np.float32)
    mat = pack_csr(indptr, cols, vals, (m, n), scheme=scheme)
    y = spmv(mat, jnp.asarray(x), interpret=True)
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(10, 300),
       n=st.integers(10, 300))
def test_spmv_property_random(seed, m, n):
    rng = np.random.default_rng(seed)
    dense, indptr, cols, vals = _random_csr(rng, m, n, 0.1)
    x = rng.standard_normal(n).astype(np.float32)
    mat = pack_csr(indptr, cols, vals, (m, n))
    y = spmv(mat, jnp.asarray(x), use_kernel=False)  # oracle path
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=1e-4, atol=1e-4)
    assert mat.padding_waste >= 1.0


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,sq,sk,hq,hkv,dh,causal,window", [
    (2, 256, 256, 4, 2, 64, True, None),
    (1, 512, 512, 2, 2, 32, True, 128),
    (2, 128, 128, 4, 1, 64, False, None),
    (1, 256, 256, 8, 8, 128, True, None),
])
def test_flash_attention_matches_oracle(b, sq, sk, hq, hkv, dh, causal,
                                        window):
    q = jax.random.normal(KEY, (b, sq, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, hkv, dh),
                          jnp.float32)
    out = mha_attention(q, k, v, causal=causal, window=window,
                        block_q=128, block_k=128, interpret=True)
    ref = mha_attention(q, k, v, causal=causal, window=window,
                        use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    q = jax.random.normal(KEY, (1, 256, 4, 64), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 2, 64), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 2, 64), dtype)
    out = mha_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    ref = mha_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), use_kernel=False)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# block-skipping flash attention
# ---------------------------------------------------------------------------

def _flash_vs_ref(bh, sq, sk, dh, causal, window, bq, bk, skip=True,
                  tol=2e-3):
    q = jax.random.normal(KEY, (bh, sq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, sk, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, sk, dh), jnp.float32)
    scale = 1.0 / (dh ** 0.5)
    out = attn_kernel.flash_attention(q, k, v, scale=scale, causal=causal,
                                      window=window, block_q=bq, block_k=bk,
                                      interpret=True, block_skipping=skip)
    ref = attention_ref(q, k, v, scale=scale, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("causal,window", [
    (True, None), (True, 96), (False, None), (False, 96),
])
@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 64), (64, 128)])
def test_block_skip_matches_dense_reference(causal, window, bq, bk):
    """The skipping kernel must be bit-for-purpose identical to the dense
    oracle across the mask grid — skipped blocks are exactly the fully
    masked ones."""
    _flash_vs_ref(2, 256, 256, 32, causal, window, bq, bk, skip=True)


@pytest.mark.parametrize("sq,sk", [
    (300, 300),      # ragged both, sq == sk (ragged prefill)
    (769, 769),      # the old divisibility-assert crash case
    (200, 456),      # sq != sk, both ragged
    (64, 320),       # aligned q, ragged-k tail masked
])
def test_flash_attention_ragged_lengths(sq, sk):
    """Tuned plans must apply to ragged prefill lengths: the q range is
    padded (tail rows sliced off) and the K/V tail masked, instead of the
    old hard `sq % block_q == 0` assert."""
    _flash_vs_ref(1, sq, sk, 32, True, None, 128, 128)
    _flash_vs_ref(1, sq, sk, 32, True, 96, 128, 128)


def test_flash_attention_ragged_gqa_through_wrapper():
    """GQA fold + ragged sq through the public mha_attention wrapper."""
    q = jax.random.normal(KEY, (2, 300, 4, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 300, 2, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 300, 2, 32), jnp.float32)
    out = mha_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    ref = mha_attention(q, k, v, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("offsets,lens,bq,bk", [
    ((0, 0, 0), (48, 30, 0), 16, 16),        # fresh slots; one empty
    ((0, 37, 5), (48, 38, 53), 16, 32),      # a riding decode slot at 37
    ((0, 20, 90), (40, 68, 100), 64, 128),   # blocks clamp to the shapes
])
def test_ragged_flash_matches_reference(offsets, lens, bq, bk):
    """Queries continuing a cache (the serve step's prefill and chunked
    prefill): per-row query offsets and valid-key counts ride scalar
    prefetch, with GQA folded through the public wrapper; a row that
    sees no key outputs 0 in kernel and oracle alike."""
    b, sq, sk, hq, hkv, dh = 3, 48, 100, 4, 2, 32
    q = jax.random.normal(KEY, (b, sq, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, hkv, dh),
                          jnp.float32)
    off = jnp.asarray(offsets, jnp.int32)
    kv_len = jnp.asarray(lens, jnp.int32)
    out = mha_attention(q, k, v, block_q=bq, block_k=bk, interpret=True,
                        q_offset=off, kv_len=kv_len)
    ref = mha_attention(q, k, v, use_kernel=False, q_offset=off,
                        kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    if lens[2] == 0:
        assert np.abs(np.asarray(out[2])).max() == 0.0


def test_flash_gqa_grouping_matches_model_attention():
    """The wrapper's GQA fold must group heads as the model does (q head j
    reads KV head j // g, `layers.attention_core`): the flash kernel then
    stands in for the model's attention, not a head-permuted variant."""
    from repro.models.layers import attention_core
    b, s, hq, hkv, dh = 2, 64, 8, 2, 32
    q = jax.random.normal(KEY, (b, s, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, dh), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    model = attention_core(q, k, v, pos, pos, causal=True, window=None,
                           scale=1.0 / dh ** 0.5)
    out = mha_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(model),
                               rtol=2e-3, atol=2e-3)


def test_fully_masked_rows_output_zero():
    """Pinned degenerate-row convention: a q row with zero surviving keys
    (reachable at sq > sk with a window) outputs 0 in both the kernel
    (skip and dense paths) and the oracle — not the uniform-softmax mean
    a raw softmax over -1e30 logits would yield."""
    bh, sq, sk, dh = 1, 456, 200, 32
    q = jax.random.normal(KEY, (bh, sq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, sk, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, sk, dh), jnp.float32)
    kw = dict(scale=0.2, causal=True, window=64, block_q=128, block_k=128,
              interpret=True)
    ref = attention_ref(q, k, v, scale=0.2, causal=True, window=64)
    # rows >= sk + window - 1 see no key at all
    assert np.abs(np.asarray(ref[:, sk + 63:])).max() == 0.0
    for skip in (True, False):
        out = attn_kernel.flash_attention(q, k, v, block_skipping=skip, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


def test_skip_and_dense_paths_agree():
    """block_skipping only removes fully-masked work: both paths must
    produce the same numbers, not just the same oracle distance."""
    q = jax.random.normal(KEY, (1, 256, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 32), jnp.float32)
    kw = dict(scale=0.17, causal=True, block_q=64, block_k=64,
              interpret=True)
    a = attn_kernel.flash_attention(q, k, v, block_skipping=True, **kw)
    b = attn_kernel.flash_attention(q, k, v, block_skipping=False, **kw)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


def test_active_block_pairs_match_mask():
    """The block-level skip law must agree with a brute-force scan of the
    element mask: a block pair is active iff any element survives."""
    for causal, window in [(True, None), (True, 50), (False, 70)]:
        sq = sk = 256
        bq, bk = 64, 32
        q_pos = np.arange(sq)[:, None]
        k_pos = np.arange(sk)[None, :]
        ok = np.ones((sq, sk), bool)
        if causal:
            ok &= q_pos >= k_pos
        if window is not None:
            ok &= (q_pos - k_pos) < window
        brute = 0
        for i in range(sq // bq):
            for j in range(sk // bk):
                brute += ok[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()
        active, total = cost_model.attention_active_block_pairs(
            sq, sk, bq, bk, causal=causal, window=window)
        assert total == (sq // bq) * (sk // bk)
        assert active == brute


def test_causal_skip_halves_counted_k_steps():
    """The measurable tentpole claim, in counted K-steps: causal prefill at
    sq=sk runs the block triangle — >= 1.5x fewer (q, k) block pairs than
    the dense grid for >= 3 q-blocks, ~2x asymptotically."""
    active, total = cost_model.attention_active_block_pairs(
        4096, 4096, 512, 512, causal=True)
    n = 4096 // 512
    assert active == n * (n + 1) // 2
    assert total / active >= 1.5


# ---------------------------------------------------------------------------
# fused decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,dh,cache_len,length,block_k", [
    (2, 4, 2, 64, 256, 256, 128),    # full cache, GQA
    (2, 4, 2, 64, 256, 100, 128),    # partial prefix
    (1, 8, 1, 32, 300, 123, 128),    # cache_len % block_k != 0
    (1, 8, 8, 32, 200, 77, 512),     # block_k > cache_len, MHA
    (1, 2, 2, 32, 96, 1, 64),        # single valid slot
])
def test_decode_kernel_matches_reference(b, hq, hkv, dh, cache_len, length,
                                         block_k):
    q = jax.random.normal(KEY, (b, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, cache_len, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, cache_len, hkv, dh),
                          jnp.float32)
    out = gqa_decode_attention(q, k, v, length=length, block_k=block_k,
                               interpret=True)
    ref = decode_ref(q, k, v, length=length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_decode_kernel_traced_length_under_jit():
    """The serving path passes `index + 1` as a traced scalar; the kernel's
    scalar-prefetch skip must work inside jit with a runtime length."""
    b, hq, hkv, dh, cache_len = 2, 4, 2, 32, 256
    q = jax.random.normal(KEY, (b, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, cache_len, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, cache_len, hkv, dh),
                          jnp.float32)
    f = jax.jit(lambda n: gqa_decode_attention(q, k, v, length=n,
                                               block_k=128, interpret=True))
    for n in (1, 100, 256):
        np.testing.assert_allclose(
            np.asarray(f(jnp.int32(n))),
            np.asarray(decode_ref(q, k, v, length=n)),
            rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("lengths,block_k", [
    ((256, 100), 128),               # mixed depths, one per sequence
    ((123, 1), 128),                 # ragged vs single valid slot
    ((300, 77, 150), 512),           # cache_len % block_k != 0, coarse block
])
def test_decode_kernel_per_row_lengths(lengths, block_k):
    """Continuous batching: every sequence sits at its own cache depth, so
    `length` is a per-sequence vector and each folded row skips its own
    tail blocks.  Must agree with the oracle at every row."""
    b, hq, hkv, dh = len(lengths), 4, 2, 32
    cache_len = 320
    q = jax.random.normal(KEY, (b, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, cache_len, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, cache_len, hkv, dh),
                          jnp.float32)
    lv = jnp.asarray(lengths, jnp.int32)
    out = gqa_decode_attention(q, k, v, length=lv, block_k=block_k,
                               interpret=True)
    ref = decode_ref(q, k, v, length=lv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    # every row must equal its scalar-length counterpart (the degenerate
    # case the vector path generalizes)
    for i, n in enumerate(lengths):
        solo = gqa_decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                    length=int(n), block_k=block_k,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(solo[0]),
                                   rtol=2e-3, atol=2e-3)


def test_decode_kernel_per_row_lengths_traced_under_jit():
    """The continuous-batching serve step carries per-slot write indexes as
    a traced vector; the per-row skip must work inside jit."""
    b, hq, hkv, dh, cache_len = 3, 4, 2, 32, 256
    q = jax.random.normal(KEY, (b, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, cache_len, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, cache_len, hkv, dh),
                          jnp.float32)
    f = jax.jit(lambda lv: gqa_decode_attention(q, k, v, length=lv,
                                                block_k=128, interpret=True))
    for lens in ((1, 100, 256), (256, 256, 256), (13, 200, 64)):
        lv = jnp.asarray(lens, jnp.int32)
        np.testing.assert_allclose(
            np.asarray(f(lv)),
            np.asarray(decode_ref(q, k, v, length=lv)),
            rtol=2e-3, atol=2e-3)


def test_decode_kernel_empty_slot_outputs_zeros():
    """A length-0 row (idle continuous-batching slot) must output zeros on
    BOTH dispatch paths — the kernel's fully-masked-row path and the
    oracle — never uniform attention onto garbage cache contents."""
    b, hq, hkv, dh, cache_len = 2, 4, 2, 32, 128
    q = jax.random.normal(KEY, (b, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, cache_len, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, cache_len, hkv, dh),
                          jnp.float32)
    lv = jnp.asarray([100, 0], jnp.int32)
    out = gqa_decode_attention(q, k, v, length=lv, block_k=64,
                               interpret=True)
    ref = decode_ref(q, k, v, length=lv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    assert np.all(np.asarray(out[1]) == 0) and np.all(np.asarray(ref[1]) == 0)
    assert np.any(np.asarray(out[0]) != 0)


def test_decode_kernel_rejects_wrong_length_shape():
    b, hq, hkv, dh, cache_len = 2, 4, 2, 32, 128
    q = jax.random.normal(KEY, (b, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, cache_len, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, cache_len, hkv, dh),
                          jnp.float32)
    with pytest.raises(ValueError):
        gqa_decode_attention(q, k, v, length=jnp.ones((b + 1,), jnp.int32),
                             interpret=True)


def test_decode_kernel_mixed_cache_dtype():
    """bf16 activations against an f32 KV cache (the serve default)."""
    b, hq, hkv, dh, cache_len = 1, 4, 2, 32, 128
    q = jax.random.normal(KEY, (b, hq, dh), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, cache_len, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, cache_len, hkv, dh),
                          jnp.float32)
    out = gqa_decode_attention(q, k, v, length=90, block_k=64,
                               interpret=True)
    ref = decode_ref(q.astype(jnp.float32), k, v, length=90)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=3e-2, atol=3e-2)
