"""Flash attention (forward) Pallas kernel, with mask-driven block skipping.

The prefill hot spot: (Sq, Sk) logits never leave VMEM.  Online-softmax
carries (m, l, acc) in VMEM scratch across the K-block grid axis; Q/K/V
blocks stream with Pallas double-buffering (eq.2's doubled B buffer again —
traffic is independent of the K-block depth, so the block sizes come from the
same VMEM-constrained solver family as the matmul kernel).

Masked work is free: each q-block's active K-step range
[`first`, `last`] is derived from the causal/sliding-window mask
(`core.cost_model.attention_step_bounds` is the shared block-level law).
The grid's K axis is sized to the *widest* active range
(`attention_max_k_steps` — a window shrinks it outright), the K/V index
maps clamp into the active range so skipped blocks are never streamed into
VMEM (Pallas elides the DMA when consecutive grid steps map to the same
block), and a `@pl.when` guard skips their FLOPs.  Causal prefill at sq=sk
runs the block triangle — ~2x fewer K-steps than the dense grid.

Ragged shapes are padded: q rows up to a block_q multiple (tail rows are
sliced off the output), K/V up to a block_k multiple (tail keys masked via
the true kv length), so tuned (block_q, block_k) plans apply to any prefill
length instead of tripping a divisibility assert.

Supports causal masking, sliding windows, and GQA (grouped q heads fold into
the q-block row axis).

`ragged_flash_attention` is the serve step's variant: a chunk of queries
that continues a KV cache.  Row r's queries sit at absolute positions
``q_offset[r] + i`` and see the first ``kv_len[r]`` cache rows, causally;
both vectors ride scalar prefetch, so each q-block's K/V stream stops at
its own last visible block (the same clamp-and-skip law, per row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hardware
from repro.core.cost_model import attention_max_k_steps

NEG_INF = -1e30


def _first_step(qi, *, block_q: int, block_k: int, k_steps: int,
                window: int | None):
    """First active K-step for q-block ``qi`` (traced mirror of
    `cost_model.attention_step_bounds`)."""
    if window is None:
        return qi * 0
    return jnp.clip((qi * block_q - window + 1) // block_k, 0, k_steps - 1)


def _last_step(qi, *, block_q: int, block_k: int, k_steps: int, causal: bool):
    """Last active K-step for q-block ``qi``."""
    if not causal:
        return qi * 0 + (k_steps - 1)
    return jnp.minimum(k_steps - 1, ((qi + 1) * block_q - 1) // block_k)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, k_steps: int, grid_k: int,
                  kv_len: int, skip: bool):
    qi = pl.program_id(1)
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if skip:
        first = _first_step(qi, block_q=block_q, block_k=block_k,
                            k_steps=k_steps, window=window)
        last = _last_step(qi, block_q=block_q, block_k=block_k,
                          k_steps=k_steps, causal=causal)
        kj = first + jj
        active = kj <= last
    else:
        kj = jj
        active = jj >= 0          # trivially true, keeps one code path

    @pl.when(active)
    def _compute():
        q = q_ref[0]                                     # (block_q, dh)
        k = k_ref[0]                                     # (block_k, dh)
        v = v_ref[0]                                     # (block_k, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_k)

        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = jnp.ones(s.shape, jnp.bool_)
        if causal:
            ok &= q_pos >= k_pos
        if window is not None:
            ok &= (q_pos - k_pos) < window
        if kv_len < k_steps * block_k:   # padded K/V tail
            ok &= k_pos < kv_len
        softmax_update(jnp.where(ok, s, NEG_INF), v, m_ref, l_ref, acc_ref)

    @pl.when(jj == grid_k - 1)
    def _store():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def softmax_update(s, v, m_ref, l_ref, acc_ref, rows=slice(None),
                   p_scale=None):
    """One online-softmax step, shared by every attention kernel here and
    in `decode.py`/`decode_int8.py`: fold the masked logits ``s``
    (q rows, keys) and their V block into the running (m, l, acc) scratch
    rows ``rows`` (a static slice; all rows by default).  ``p_scale``
    (1, keys), if given, multiplies the probabilities' columns before the
    PV product — the int8 cache's per-key V scales."""
    m_prev = m_ref[rows, :]                              # (q rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    # Rows with no surviving key yet sit at m == NEG_INF; exp(s - m)
    # would turn fully-masked logits into 1s.  Zero them so l stays 0
    # and the store's l-floor makes such rows output 0 — the pinned
    # convention for degenerate rows (padded q/K tails, and window
    # rows beyond the cache at sq > sk), shared with `ref.attention_ref`.
    p = jnp.where(m_new <= NEG_INF, 0.0, p)
    corr = jnp.exp(m_prev - m_new)
    l_ref[rows, :] = l_ref[rows, :] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[rows, :] = m_new
    if p_scale is not None:
        p = p * p_scale
    acc_ref[rows, :] = acc_ref[rows, :] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, causal: bool = True,
                    window: int | None = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False,
                    block_skipping: bool = True) -> jax.Array:
    """q: (BH, Sq, dh); k, v: (BH, Sk, dh) — heads pre-folded into batch.

    GQA callers tile/fold so q and kv agree on the BH axis (see ops.py).
    ``block_skipping=False`` forces the dense every-block grid (the
    pre-skipping kernel) — kept for A/B benchmarking of the skip credit.
    """
    bh, sq, dh = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    q_pad = -sq % block_q
    k_pad = -sk % block_k
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0)))
    sq_p, sk_p = sq + q_pad, sk + k_pad
    k_steps = sk_p // block_k
    q_blocks = sq_p // block_q

    skip = block_skipping and (causal or window is not None)
    # The grid's K axis covers only the widest active range; per-q-block
    # offsets and @pl.when guards do the rest.  Bounds use the padded q
    # range so tail (sliced-off) rows stay inside the grid.
    grid_k = (attention_max_k_steps(sq_p, sk_p, block_q, block_k,
                                    causal=causal, window=window)
              if skip else k_steps)
    grid = (bh, q_blocks, grid_k)

    if skip:
        def kv_index(b, i, j):
            first = _first_step(i, block_q=block_q, block_k=block_k,
                                k_steps=k_steps, window=window)
            last = _last_step(i, block_q=block_q, block_k=block_k,
                              k_steps=k_steps, causal=causal)
            # Clamp into the active range: out-of-range grid steps revisit
            # the last active block, so Pallas never streams it again.
            return (b, jnp.minimum(first + j, last), 0)
    else:
        def kv_index(b, i, j):
            return (b, j, 0)

    fn = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, k_steps=k_steps, grid_k=grid_k,
        kv_len=sk, skip=skip)
    out = pl.pallas_call(
        fn,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), kv_index),
            pl.BlockSpec((1, block_k, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=hardware.TPU_V5E.usable_vmem()),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq] if q_pad else out


def _ragged_last_step(qi, off, kv_len, *, block_q: int, block_k: int,
                      k_steps: int):
    """Last K-step q-block ``qi`` of a row at offset ``off`` with
    ``kv_len`` valid keys can see (0 when it sees none: that block runs
    fully masked and its rows output 0)."""
    hi = jnp.minimum(off + (qi + 1) * block_q, kv_len) - 1
    return jnp.clip(hi // block_k, 0, k_steps - 1)


def _ragged_flash_kernel(off_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float,
                         block_q: int, block_k: int, k_steps: int):
    r = pl.program_id(0)
    qi = pl.program_id(1)
    jj = pl.program_id(2)
    off = off_ref[r]
    kv_len = len_ref[r]

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    last = _ragged_last_step(qi, off, kv_len, block_q=block_q,
                             block_k=block_k, k_steps=k_steps)

    @pl.when(jj <= last)
    def _compute():
        v = v_ref[0]
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_k)
        q_pos = off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = jj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (q_pos >= k_pos) & (k_pos < kv_len)
        softmax_update(jnp.where(ok, s, NEG_INF), v, m_ref, l_ref, acc_ref)

    @pl.when(jj == k_steps - 1)
    def _store():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def ragged_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           q_offset: jax.Array, kv_len: jax.Array, *,
                           scale: float, block_q: int = 512,
                           block_k: int = 512,
                           interpret: bool = False) -> jax.Array:
    """Causal attention of queries that continue a cache.

    q: (BH, Sq, dh) — row r's query i sits at position ``q_offset[r] + i``;
    k, v: (BH, Sk, dh) — the cache, of which the first ``kv_len[r]`` rows
    are valid for row r.  ``q_offset``/``kv_len``: (BH,) int32.  A query
    sees key j iff ``j <= q_pos`` and ``j < kv_len``; a query that sees no
    key outputs 0.  K/V blocks past a q-block's last visible one are
    neither streamed nor multiplied (the index map clamps onto it).
    """
    bh, sq, dh = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    q_pad = -sq % block_q
    k_pad = -sk % block_k
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0)))
    k_steps = (sk + k_pad) // block_k
    q_blocks = (sq + q_pad) // block_q

    def kv_index(r, i, j, off_ref, len_ref):
        last = _ragged_last_step(i, off_ref[r], len_ref[r], block_q=block_q,
                                 block_k=block_k, k_steps=k_steps)
        return (r, jnp.minimum(j, last), 0)

    def q_index(r, i, j, off_ref, len_ref):
        return (r, i, 0)

    fn = functools.partial(_ragged_flash_kernel, scale=scale,
                           block_q=block_q, block_k=block_k, k_steps=k_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, q_blocks, k_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), q_index),
            pl.BlockSpec((1, block_k, dh), kv_index),
            pl.BlockSpec((1, block_k, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        fn,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq + q_pad, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=hardware.TPU_V5E.usable_vmem()),
        interpret=interpret,
    )(jnp.asarray(q_offset, jnp.int32), jnp.asarray(kv_len, jnp.int32),
      q, k, v)
    return out[:, :sq] if q_pad else out
