"""Int8 quantized-streaming variant of the fused decode-attention kernel.

Same online-softmax / per-row valid-prefix-skip structure as
`decode.py`, but the KV cache is stored and **streamed as int8** with
one f32 scale per token row per KV head (`runtime/quantize.py`):
each grid step fetches an int8 K/V block plus its block of scales,
dequantizes **in register** (the Pallas int8 pattern: upcast once in
VMEM, never in HBM; the contiguous kernel folds its lane-dense scale
row into the logits and probabilities, the paged kernel multiplies each
head's rows by their scale column), and accumulates in f32.  Streamed
bytes per token per KV head drop from ``2 * dh * itemsize`` to
``dh + 4`` for each of K and V — ~1.88x at dh = 64 — at a bounded
accuracy cost (half a quantization step per element, see the quantize
module; the `decode_int8` bench row gates both numbers in CI).

Tokens are quantized once at cache-write time (`models/layers.py`
scatter-on-write), so this kernel never quantizes — it only streams and
dequantizes.  The q rows stay in float and the contraction accumulates
in f32 (`preferred_element_type`), mirroring the bf16-stream /
f32-accumulate matmul path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hardware
from repro.kernels.attention.decode import (_attend_rows, _row_lengths,
                                            decode_ref)
from repro.kernels.attention.kernel import NEG_INF, softmax_update
from repro.runtime import quantize


def _quantized_decode_kernel(len_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                             o_ref, m_ref, l_ref, acc_ref, *,
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
        q = q_ref[0].astype(jnp.float32)                 # (g, dh)
        # In-register dequant, folded onto the block's key axis: the
        # per-row scales arrive as a lane-dense (1, block_k) row, so K's
        # scale multiplies the logits' columns and V's the probabilities'
        # columns — (q . kq) * ks == q . (kq * ks), likewise for V.
        ks = ks_ref[0]                                   # (1, block_k)
        vs = vs_ref[0]
        s = jax.lax.dot_general(
            q, kq_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (ks * scale)
        k_pos = jj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        softmax_update(jnp.where(k_pos < length, s, NEG_INF),
                       vq_ref[0].astype(jnp.float32), m_ref, l_ref, acc_ref,
                       p_scale=vs)

    @pl.when(jj == k_steps - 1)
    def _store():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def quantized_decode_attention(q: jax.Array, kq: jax.Array, ks: jax.Array,
                               vq: jax.Array, vs: jax.Array, *,
                               scale: float, length, block_k: int = 512,
                               interpret: bool = False) -> jax.Array:
    """q: (BKV, g, dh) float; kq, vq: (BKV, L, dh) int8;
    ks, vs: (BKV, L) f32 per-row scales; length as in `decode_attention`.

    The int8 cache is streamed verbatim — the q rows are NOT upcast to
    the cache dtype (that is the whole point); they run in f32 against
    the in-register dequantized blocks.
    """
    out_dtype = q.dtype
    q = q.astype(jnp.float32)
    bkv, g, dh = q.shape
    _, kl, _ = kq.shape
    block_k = min(block_k, kl)
    k_pad = -kl % block_k
    if k_pad:
        kq = jnp.pad(kq, ((0, 0), (0, k_pad), (0, 0)))
        vq = jnp.pad(vq, ((0, 0), (0, k_pad), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, k_pad)))
        vs = jnp.pad(vs, ((0, 0), (0, k_pad)))
    k_steps = (kl + k_pad) // block_k
    lengths = _row_lengths(length, bkv, kl)

    def kv_index(b, j, len_ref):
        last = jnp.maximum(0, (len_ref[b] - 1) // block_k)
        return (b, jnp.minimum(j, last), 0)

    def scale_index(b, j, len_ref):
        last = jnp.maximum(0, (len_ref[b] - 1) // block_k)
        return (b, 0, jnp.minimum(j, last))

    # (BKV, 1, L): the scale block's last two dims are then (1, block_k),
    # which Mosaic tiles (a (1, block_k) block over (BKV, L) it refuses).
    ks = ks[:, None, :]
    vs = vs[:, None, :]

    fn = functools.partial(_quantized_decode_kernel, scale=scale,
                           block_k=block_k, k_steps=k_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bkv, k_steps),
        in_specs=[
            pl.BlockSpec((1, g, dh), lambda b, j, len_ref: (b, 0, 0)),
            pl.BlockSpec((1, block_k, dh), kv_index),
            pl.BlockSpec((1, 1, block_k), scale_index),
            pl.BlockSpec((1, block_k, dh), kv_index),
            pl.BlockSpec((1, 1, block_k), scale_index),
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
        out_shape=jax.ShapeDtypeStruct((bkv, g, dh), jnp.float32),
        name="quantized_decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hardware.TPU_V5E.usable_vmem()),
        interpret=interpret,
    )(lengths, q, kq, ks, vq, vs)
    return out.astype(out_dtype)


def quantized_gqa_decode_attention(q: jax.Array, kq: jax.Array,
                                   ks: jax.Array, vq: jax.Array,
                                   vs: jax.Array, *, length,
                                   scale: float | None = None,
                                   block_k: int = 512,
                                   interpret: bool = False) -> jax.Array:
    """q: (B, Hq, dh); kq, vq: (B, L, Hkv, dh) int8;
    ks, vs: (B, L, Hkv) f32 -> (B, Hq, dh).

    The GQA fold mirrors `gqa_decode_attention`: the group becomes the
    q-row axis per KV head and the int8 cache (with its scales) is
    streamed once per KV head, never repeated.
    """
    b, hq, dh = q.shape
    _, kl, hkv, _ = kq.shape
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
    kqf = kq.transpose(0, 2, 1, 3).reshape(b * hkv, kl, dh)
    vqf = vq.transpose(0, 2, 1, 3).reshape(b * hkv, kl, dh)
    ksf = ks.transpose(0, 2, 1).reshape(b * hkv, kl)
    vsf = vs.transpose(0, 2, 1).reshape(b * hkv, kl)
    out = quantized_decode_attention(qf, kqf, ksf, vqf, vsf, scale=scale,
                                     length=length, block_k=block_k,
                                     interpret=interpret)
    return out.reshape(b, hkv, g, dh).reshape(b, hq, dh)


def _paged_quantized_decode_kernel(len_ref, pt_ref, q_ref, kq_ref, ks_ref,
                                   vq_ref, vs_ref, o_ref,
                                   m_ref, l_ref, acc_ref, *,
                                   scale: float, page_size: int,
                                   max_pages: int, hkv: int):
    """Paged variant: the k axis walks the slot's page table (the index
    maps translate grid step -> physical pool page, as in
    `_paged_decode_kernel`); each fetched page carries every KV head, and
    head h's int8 rows dequantize in register against their
    (page_size, 1) scale column."""
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
            _attend_rows(q_ref[0, rows, :], kq_ref[0, :, h, :],
                         vq_ref[0, :, h, :], rows, jj * page_size, length,
                         m_ref, l_ref, acc_ref, scale=scale,
                         k_scale=ks_ref[0, :, h:h + 1],
                         v_scale=vs_ref[0, :, h:h + 1])

    @pl.when(jj == max_pages - 1)
    def _store():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_quantized_gqa_decode_attention(
        q: jax.Array, kq_pool: jax.Array, ks_pool: jax.Array,
        vq_pool: jax.Array, vs_pool: jax.Array, pages: jax.Array, *,
        length, scale: float | None = None,
        interpret: bool = False) -> jax.Array:
    """Fused decode attention through an int8 paged KV cache.

    q: (B, Hq, dh); kq_pool, vq_pool: (num_pages, page_size, Hkv, dh)
    int8; ks_pool, vs_pool: (num_pages, page_size, Hkv) f32 per-row
    scales; pages: (B, max_pages) int32 page table; length: (B,) valid
    prefixes.  Returns (B, Hq, dh).  Page-table translation, the per-slot
    skip law and the one-slot-per-grid-row blocking are identical to
    `paged_gqa_decode_attention`; the scale pools ride two extra inputs
    whose blocks drop the dh axis.
    """
    out_dtype = q.dtype
    q = q.astype(jnp.float32)
    b, hq, dh = q.shape
    num_pages, page_size, hkv, _ = kq_pool.shape
    max_pages = pages.shape[1]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    lengths = _row_lengths(length, b, max_pages * page_size)
    pt = jnp.asarray(pages, jnp.int32)

    def page_of(r, j, len_ref, pt_ref):
        last = jnp.maximum(0, (len_ref[r] - 1) // page_size)
        return jnp.clip(pt_ref[r, jnp.minimum(j, last)], 0, num_pages - 1)

    def kv_index(r, j, len_ref, pt_ref):
        return (page_of(r, j, len_ref, pt_ref), 0, 0, 0)

    def scale_index(r, j, len_ref, pt_ref):
        return (page_of(r, j, len_ref, pt_ref), 0, 0)

    fn = functools.partial(_paged_quantized_decode_kernel, scale=scale,
                           page_size=page_size, max_pages=max_pages, hkv=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec((1, hq, dh), lambda r, j, len_ref, pt_ref: (r, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, dh), kv_index),
            pl.BlockSpec((1, page_size, hkv), scale_index),
            pl.BlockSpec((1, page_size, hkv, dh), kv_index),
            pl.BlockSpec((1, page_size, hkv), scale_index),
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
        out_shape=jax.ShapeDtypeStruct((b, hq, dh), jnp.float32),
        name="paged_quantized_decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hardware.TPU_V5E.usable_vmem()),
        interpret=interpret,
    )(lengths, pt, q, kq_pool, ks_pool, vq_pool, vs_pool)
    return out.astype(out_dtype)


def quantized_decode_ref(q: jax.Array, kq: jax.Array, ks: jax.Array,
                         vq: jax.Array, vs: jax.Array, *, length,
                         scale: float | None = None) -> jax.Array:
    """Pure-jnp oracle: dequantize the whole cache (what the kernel does
    block-by-block in register), then reuse `decode_ref`."""
    k = quantize.dequantize_rows(kq, ks)
    v = quantize.dequantize_rows(vq, vs)
    return decode_ref(q.astype(jnp.float32), k, v, length=length,
                      scale=scale).astype(q.dtype)


def paged_quantized_decode_ref(q: jax.Array, kq_pool: jax.Array,
                               ks_pool: jax.Array, vq_pool: jax.Array,
                               vs_pool: jax.Array, pages: jax.Array, *,
                               length,
                               scale: float | None = None) -> jax.Array:
    """Oracle for the paged variant: gather each slot's pages (values and
    scales) into a contiguous view, dequantize, `decode_ref`."""
    b = q.shape[0]
    num_pages, page_size, hkv, dh = kq_pool.shape
    max_pages = pages.shape[1]
    safe = jnp.clip(jnp.asarray(pages, jnp.int32), 0, num_pages - 1)
    kg = kq_pool[safe].reshape(b, max_pages * page_size, hkv, dh)
    vg = vq_pool[safe].reshape(b, max_pages * page_size, hkv, dh)
    ksg = ks_pool[safe].reshape(b, max_pages * page_size, hkv)
    vsg = vs_pool[safe].reshape(b, max_pages * page_size, hkv)
    lv = jnp.asarray(length, jnp.int32)
    if lv.ndim == 0:
        lv = jnp.full((b,), lv, jnp.int32)
    return quantized_decode_ref(q, kg, ksg, vg, vsg, length=lv, scale=scale)
