"""Fused single-query decode-attention Pallas kernel (KV-cache resident).

One generated token per sequence attends over the whole KV cache — the
serving decode hot loop.  The jnp path materializes (B, H, 1, L) logits and
re-reads the cache per head group; this kernel fuses qK^T -> online softmax
-> pV into one pass that streams each K/V block exactly once.

GQA head folding: the ``g = Hq/Hkv`` query heads sharing a KV head become
the q-*row* axis of a (g, dh) block, so the MXU contraction amortizes the
K/V stream across the whole group (the same fold the prefill kernel gets
from `ops.mha_attention`, but per KV head instead of per q head — decode
must not `jnp.repeat` the cache).

Cache-length skipping: the valid prefix length is a traced value at
serving time, so it rides a scalar-prefetch argument — one int32 *per
folded row* (continuous batching gives every sequence its own prefix; a
shared scalar is the degenerate broadcast case).  For each row, the K/V
index maps clamp every grid step past its last valid block onto it (Pallas
elides the repeated DMA) and a `@pl.when` guard skips the FLOPs — blocks
past a row's write index are neither streamed nor multiplied, the decode
analogue of the prefill kernel's causal block triangle.  Cache lengths not
divisible by block_k are padded once at the call site and masked via the
same per-row length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hardware
from repro.kernels.attention.kernel import NEG_INF, softmax_update


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   scale: float, block_k: int, k_steps: int):
    bb = pl.program_id(0)
    jj = pl.program_id(1)
    length = len_ref[bb]
    last = jnp.maximum(0, (length - 1) // block_k)

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jj <= last)
    def _compute():
        q = q_ref[0]                                     # (g, dh)
        k = k_ref[0]                                     # (block_k, dh)
        v = v_ref[0]                                     # (block_k, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (g, block_k)
        k_pos = jj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        softmax_update(jnp.where(k_pos < length, s, NEG_INF), v,
                       m_ref, l_ref, acc_ref)

    @pl.when(jj == k_steps - 1)
    def _store():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _row_lengths(length, rows: int, kl: int) -> jax.Array:
    """Normalize ``length`` (python int / traced scalar / per-row vector)
    to a clamped int32 vector of one valid-prefix length per folded row —
    the scalar-prefetch payload.  The scalar case is the degenerate
    uniform broadcast."""
    lv = jnp.asarray(length, jnp.int32)
    if lv.ndim == 0:
        lv = jnp.full((rows,), lv, jnp.int32)
    elif lv.shape != (rows,):
        raise ValueError(
            f"length must be a scalar or a ({rows},) per-row vector, "
            f"got shape {lv.shape}")
    return jnp.minimum(lv, kl)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     scale: float, length, block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (BKV, g, dh); k, v: (BKV, L, dh); length: valid cache prefix.

    ``length`` may be a python int, a traced int32 scalar (the serving
    cache index + 1), or a per-row int32 vector of shape (BKV,) — the
    continuous-batching case where every sequence sits at its own depth.
    Keys at positions >= the row's length are masked and their blocks
    skipped per row.  The KV-head fold (BKV = B * Hkv) is the caller's
    job — see `gqa_decode_attention`.
    """
    out_dtype = q.dtype
    if q.dtype != k.dtype:
        # The q rows are tiny; upcasting them to the cache dtype is free
        # (serving keeps an f32/bf16 cache while activations may differ).
        # The output is cast back so the kernel and oracle paths agree.
        q = q.astype(k.dtype)
    bkv, g, dh = q.shape
    _, kl, _ = k.shape
    block_k = min(block_k, kl)
    k_pad = -kl % block_k
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0)))
    k_steps = (kl + k_pad) // block_k
    lengths = _row_lengths(length, bkv, kl)

    def kv_index(b, j, len_ref):
        last = jnp.maximum(0, (len_ref[b] - 1) // block_k)
        return (b, jnp.minimum(j, last), 0)

    fn = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                           k_steps=k_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bkv, k_steps),
        in_specs=[
            pl.BlockSpec((1, g, dh), lambda b, j, len_ref: (b, 0, 0)),
            pl.BlockSpec((1, block_k, dh), kv_index),
            pl.BlockSpec((1, block_k, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, g, dh), lambda b, j, len_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        fn,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bkv, g, dh), q.dtype),
        name="decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hardware.TPU_V5E.usable_vmem()),
        interpret=interpret,
    )(lengths, q, k, v)
    return out.astype(out_dtype)


def gqa_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         length, scale: float | None = None,
                         block_k: int = 512,
                         interpret: bool = False) -> jax.Array:
    """q: (B, Hq, dh); k, v: (B, L, Hkv, dh) -> (B, Hq, dh).

    Folds the GQA group into the q-row axis per KV head (no cache repeat)
    and dispatches to the fused kernel.  ``length`` is a scalar or a (B,)
    per-sequence vector; the fold repeats it across each sequence's KV
    heads (row b*Hkv+h belongs to sequence b).
    """
    b, hq, dh = q.shape
    _, kl, hkv, _ = k.shape
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    lv = jnp.asarray(length, jnp.int32)
    if lv.ndim == 1:
        if lv.shape != (b,):
            raise ValueError(
                f"length must be a scalar or a ({b},) per-sequence vector, "
                f"got shape {lv.shape}")
        length = jnp.repeat(lv, hkv)
    qf = q.reshape(b, hkv, g, dh).reshape(b * hkv, g, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, kl, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, kl, dh)
    out = decode_attention(qf, kf, vf, scale=scale, length=length,
                           block_k=block_k, interpret=interpret)
    return out.reshape(b, hkv, g, dh).reshape(b, hq, dh)


def _attend_rows(q, k, v, rows, k0, length, m_ref, l_ref, acc_ref, *,
                 scale: float, k_scale=None, v_scale=None):
    """One online-softmax update of the scratch ``rows`` (static slice of
    the q-row axis) against one streamed K/V block starting at absolute
    key position ``k0``.  ``k_scale``/``v_scale`` are the int8 layout's
    per-row (block, 1) dequantization columns (None for a float cache)."""
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale
        v = v.astype(jnp.float32) * v_scale
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (g, block)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    softmax_update(jnp.where(k_pos < length, s, NEG_INF), v,
                   m_ref, l_ref, acc_ref, rows=rows)


def _paged_decode_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *,
                         scale: float, page_size: int, max_pages: int,
                         hkv: int):
    """Same online-softmax body as `_decode_kernel`, but the grid's k axis
    walks the slot's *page table* instead of a contiguous cache: grid step
    j streams physical page ``pt_ref[slot, j]`` (the index maps below do
    the translation; ``pt_ref`` itself is unused here but must ride the
    scalar-prefetch signature).  One grid row is one slot: the page block
    carries every KV head (a (1, page_size, Hkv, dh) block is the only
    one Mosaic tiles, its last two dims being whole), and the kernel
    selects head h for the q rows of its GQA group."""
    del pt_ref
    bb = pl.program_id(0)
    jj = pl.program_id(1)
    length = len_ref[bb]
    last = jnp.maximum(0, (length - 1) // page_size)
    g = q_ref.shape[1] // hkv

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jj <= last)
    def _compute():
        for h in range(hkv):
            rows = slice(h * g, (h + 1) * g)
            _attend_rows(q_ref[0, rows, :], k_ref[0, :, h, :],
                         v_ref[0, :, h, :], rows, jj * page_size, length,
                         m_ref, l_ref, acc_ref, scale=scale)

    @pl.when(jj == max_pages - 1)
    def _store():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_gqa_decode_attention(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, pages: jax.Array, *,
                               length, scale: float | None = None,
                               interpret: bool = False) -> jax.Array:
    """Fused decode attention through a paged KV cache.

    q: (B, Hq, dh); k_pool, v_pool: (num_pages, page_size, Hkv, dh) —
    the layer's shared physical page pools; pages: (B, max_pages) int32
    per-slot page table (-1 = unassigned); length: (B,) valid-prefix
    token counts.  Returns (B, Hq, dh).

    The page table rides the *second* scalar-prefetch argument next to
    the lengths vector: the K/V BlockSpec index maps read
    ``pages[slot, min(j, last)]`` to pick the physical pool page each grid
    step streams, so a slot touches exactly its own pages — blocks past a
    slot's depth are neither streamed nor multiplied, same skip law as
    the contiguous kernel, and unassigned (-1) entries are never reached
    because ``j`` is clamped to the slot's last valid page.  Each grid
    row is one slot and streams each of its pages once for all KV heads;
    the GQA group of KV head h is q rows ``h*g:(h+1)*g`` (the pool has
    no batch axis to fold — that is the whole point).
    """
    out_dtype = q.dtype
    if q.dtype != k_pool.dtype:
        q = q.astype(k_pool.dtype)
    b, hq, dh = q.shape
    num_pages, page_size, hkv, _ = k_pool.shape
    max_pages = pages.shape[1]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    lengths = _row_lengths(length, b, max_pages * page_size)
    pt = jnp.asarray(pages, jnp.int32)

    def kv_index(r, j, len_ref, pt_ref):
        last = jnp.maximum(0, (len_ref[r] - 1) // page_size)
        page = pt_ref[r, jnp.minimum(j, last)]
        # Clamp keeps even a pathological table in bounds; the length
        # mask already zeroes anything past the valid prefix.
        return (jnp.clip(page, 0, num_pages - 1), 0, 0, 0)

    fn = functools.partial(_paged_decode_kernel, scale=scale,
                           page_size=page_size, max_pages=max_pages,
                           hkv=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec((1, hq, dh), lambda r, j, len_ref, pt_ref: (r, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, dh), kv_index),
            pl.BlockSpec((1, page_size, hkv, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, hq, dh),
                               lambda r, j, len_ref, pt_ref: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        fn,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dh), q.dtype),
        name="paged_decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hardware.TPU_V5E.usable_vmem()),
        interpret=interpret,
    )(lengths, pt, q, k_pool, v_pool)
    return out.astype(out_dtype)


def paged_decode_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     pages: jax.Array, *, length,
                     scale: float | None = None) -> jax.Array:
    """Pure-jnp oracle for `paged_gqa_decode_attention`: gather each
    slot's pages back into a contiguous view, then reuse `decode_ref`."""
    b = q.shape[0]
    num_pages, page_size, hkv, dh = k_pool.shape
    max_pages = pages.shape[1]
    safe = jnp.clip(jnp.asarray(pages, jnp.int32), 0, num_pages - 1)
    kg = k_pool[safe].reshape(b, max_pages * page_size, hkv, dh)
    vg = v_pool[safe].reshape(b, max_pages * page_size, hkv, dh)
    lv = jnp.asarray(length, jnp.int32)
    if lv.ndim == 0:
        lv = jnp.full((b,), lv, jnp.int32)
    return decode_ref(q, kg, vg, length=lv, scale=scale)


def decode_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
               length, scale: float | None = None) -> jax.Array:
    """Pure-jnp oracle for `gqa_decode_attention` (materialized logits).
    ``length`` is a scalar or a (B,) per-sequence vector."""
    b, hq, dh = q.shape
    _, kl, hkv, _ = k.shape
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    qr = q.reshape(b, hkv, g, dh).astype(jnp.float32)
    kr = k.transpose(0, 2, 1, 3).astype(jnp.float32)    # (b, hkv, kl, dh)
    vr = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qr, kr) * scale
    lv = jnp.asarray(length, jnp.int32)
    if lv.ndim == 0:
        lv = jnp.full((b,), lv, jnp.int32)
    valid = jnp.arange(kl)[None, :] < lv[:, None]       # (b, kl)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, vr)
    # A slot with no valid keys (length 0 — an idle continuous-batching
    # slot) outputs zeros, matching the kernel's fully-masked-row path;
    # softmax over an all-masked row would otherwise fabricate uniform
    # attention onto garbage cache contents.
    out = jnp.where((lv > 0)[:, None, None, None], out, 0.0)
    return out.reshape(b, hq, dh).astype(q.dtype)
