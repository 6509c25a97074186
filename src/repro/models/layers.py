"""Shared neural-net layers (functional JAX): norms, RoPE, GQA attention,
SwiGLU/GELU MLPs, embeddings.

Everything is init/apply pairs over plain dict pytrees; layer stacks hold
*stacked* params (leading layer axis) so the model can `lax.scan` over depth.
Sharding is expressed through logical-axis constraints (`parallel.sharding`).
"""

from __future__ import annotations

import math
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import constrain
from repro.runtime import quantize

Params = dict
DEFAULT_INIT_SCALE = 0.02


def _dense_init(key, shape, scale=DEFAULT_INIT_SCALE, dtype=jnp.float32):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int) -> Params:
    # f32 at any storage dtype: `rmsnorm` reads the scale in f32.
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)                     # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs    # (..., seq, half)
    cos = jnp.cos(angles)[..., :, None, :]                           # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (options: qk-norm, qkv-bias, sliding window, non-causal)
# ---------------------------------------------------------------------------

def attention_init(key, cfg, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (cfg.d_model, cfg.q_dim), dtype=dtype),
        "wk": _dense_init(ks[1], (cfg.d_model, cfg.kv_dim), dtype=dtype),
        "wv": _dense_init(ks[2], (cfg.d_model, cfg.kv_dim), dtype=dtype),
        "wo": _dense_init(ks[3], (cfg.q_dim, cfg.d_model), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.head_dim)
        p["k_norm"] = rmsnorm_init(cfg.head_dim)
    return p


def attention_param_specs(cfg) -> Params:
    """Logical axes per attention param leaf (mirrors attention_init)."""
    p = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        p.update({"bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",)})
    if cfg.qk_norm:
        p["q_norm"] = {"scale": (None,)}
        p["k_norm"] = {"scale": (None,)}
    return p


def _mask_block(q_pos, k_pos, causal: bool, window: int | None,
                k_valid=None):
    """Boolean mask from position vectors.

    Every operand may be shared across the batch (1-D: ``q_pos (Sq,)``,
    ``k_pos (Sk,)``, ``k_valid (Sk,)``) or per-sequence (2-D with a
    leading batch axis) — ragged continuous batching gives each slot its
    own positions and valid cache prefix.  Returns ``(Sq, Sk)`` when all
    operands are shared, ``(B, Sq, Sk)`` otherwise.
    """
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(diff.shape, dtype=bool)
    if causal:
        ok &= diff >= 0
    if window is not None:
        ok &= diff < window
    if k_valid is not None:
        ok = ok & k_valid[..., None, :]
    return ok


def attention_core(q, k, v, q_pos, k_pos, *, causal, window, scale,
                   k_valid=None, chunk_q: int | None = None,
                   unroll: bool = False, remat_chunks: bool = False):
    """Memory-safe multi-head attention with GQA grouping.

    q: (B,Sq,Hq,dh), k/v: (B,Sk,Hkv,dh), q_pos: (Sq,), k_pos: (Sk,).
    ``q_pos``/``k_pos``/``k_valid`` may also carry a leading batch axis
    ((B, Sq) / (B, Sk)) — the ragged continuous-batching decode path, where
    every slot sits at its own cache depth and masks its own prefix.
    When ``chunk_q`` divides Sq, query blocks are processed sequentially with
    `lax.scan` so the (Sq, Sk) logits never materialize — the jnp analogue of
    the Pallas flash-attention kernel's VMEM blocking.
    """
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qr = q.reshape(b, sq, hkv, g, dh)

    def blk(q_blk, qp_blk):
        # bf16 operands, f32 accumulation — no f32 copies of Q/K/V in HBM.
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k,
                            preferred_element_type=jnp.float32) * scale
        mask = _mask_block(qp_blk, k_pos, causal, window, k_valid)
        # (q, k) masks are shared across (b, h, g); (b, q, k) masks are
        # per-sequence and broadcast over (h, g) only.
        mask = (mask[None, None, None] if mask.ndim == 2
                else mask[:, None, None])
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    if chunk_q and sq > chunk_q and sq % chunk_q == 0:
        nchunks = sq // chunk_q
        qc = jnp.moveaxis(qr.reshape(b, nchunks, chunk_q, hkv, g, dh), 1, 0)
        pc = (jnp.moveaxis(q_pos.reshape(b, nchunks, chunk_q), 1, 0)
              if q_pos.ndim == 2 else q_pos.reshape(nchunks, chunk_q))
        fn = blk
        if remat_chunks and not unroll:
            # backward recomputes each chunk's logits/probs instead of
            # saving nchunks of them (flash-attention-style memory)
            fn = jax.checkpoint(blk, prevent_cse=False)
        if unroll:
            # python loop: identical math, fully visible to cost analysis
            out = jnp.stack([blk(qc[i], pc[i]) for i in range(nchunks)])
        else:
            _, out = jax.lax.scan(lambda c, xs: (c, fn(*xs)), None, (qc, pc))
        out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hkv, g, dh)
    else:
        out = blk(qr, q_pos)
    return out.reshape(b, sq, hq, dh)


def attention_apply(
    params: Params,
    x: jax.Array,                       # (B, S, D)
    cfg,
    positions: jax.Array,               # (S,) or (B, S) int32 abs positions
    cache: Params | None = None,        # {"k","v": (B, S_cache, Hkv, dh)}
    lengths: jax.Array | None = None,   # (B,) per-slot valid cache prefix
    active: jax.Array | None = None,    # (B,) or (B, S) write/advance mask
    chunk_q: int | None = None,
    prefill: bool = False,              # serving prefill (fwd-only, no grad)
    pages: jax.Array | None = None,     # (B, max_pages) int32 page table
    paged=None,                         # runtime.paging.PageSpec (static)
) -> tuple[jax.Array, Params | None]:
    from repro.parallel.sharding import gather_weight
    b, s, _ = x.shape
    q = x @ gather_weight(params["wq"]).astype(x.dtype)
    k = x @ gather_weight(params["wk"]).astype(x.dtype)
    v = x @ gather_weight(params["wv"]).astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    # Shared (S,) positions broadcast across the batch; per-slot (B, S)
    # positions (ragged decode) index each sequence at its own depth.
    pos_b = positions if positions.ndim == 2 else positions[None]
    q = apply_rope(q, pos_b, cfg.rope_theta)
    k = apply_rope(k, pos_b, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", None)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if chunk_q is None:
        if cfg.attn_chunk == 0:
            chunk_q = None
        elif cfg.attn_chunk > 0:
            chunk_q = cfg.attn_chunk
        elif s > 2048:
            chunk_q = 512

    if cache is None:
        k = constrain(k, "batch", "seq", "kv_heads", None)
        v = constrain(v, "batch", "seq", "kv_heads", None)
        if prefill and jax.default_backend() == "tpu":
            # Serving prefill: the forward-only hot spot goes through the
            # registry's autotuned flash kernel (analytic plan at trace
            # time — the cache was pre-warmed by `autotune.plan_for_model`).
            # Training keeps the differentiable jnp path below.
            from repro.kernels.autotune import dispatch
            out = dispatch("attention", q, k, v, causal=cfg.causal,
                           window=cfg.sliding_window)
        else:
            out = attention_core(q, k, v, positions, positions,
                                 causal=cfg.causal,
                                 window=cfg.sliding_window, scale=scale,
                                 chunk_q=chunk_q, unroll=cfg.probe_unroll,
                                 remat_chunks=(cfg.remat == "full"))
        new_cache = None
    else:
        # Decode: every slot writes its new K/V at its OWN depth
        # (`lengths[b]`; ring-buffer modulo for SWA) and attends only over
        # its own valid cache prefix — ragged continuous batching.  A shared
        # scalar depth is the degenerate case where `lengths` is uniform.
        # ``active`` may be (B,) — the slot writes/advances all S positions
        # — or (B, S) — chunked prefill, where each admitted slot writes
        # only its own prompt's prefix of the packed chunk.
        # An int8 cache carries parallel per-token-row scale leaves
        # (runtime/quantize.py): tokens are quantized ONCE here at
        # write time, and reads either stream q+scale through the
        # quantized fused kernel or dequantize for the jnp fallback.
        ck, cv = cache["k"], cache["v"]
        quantized = "k_scale" in cache
        if quantized:
            kq_w, ks_w = quantize.quantize_rows(k)
            vq_w, vs_w = quantize.quantize_rows(v)
            cks, cvs = cache["k_scale"], cache["v_scale"]
        if lengths is None:
            lengths = jnp.zeros((b,), jnp.int32)
        if active is None:
            act2d = jnp.ones((b, s), bool)
        else:
            act = jnp.asarray(active).astype(bool)
            act2d = (act if act.ndim == 2
                     else jnp.broadcast_to(act[:, None], (b, s)))
        t_abs = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)  # (B, S)
        # Valid prefix after the write, per slot (inactive: unchanged).
        new_len = lengths + jnp.sum(act2d, axis=1, dtype=jnp.int32)
        mode = os.environ.get("REPRO_DECODE_KERNEL", "auto")
        kernels_on = (cfg.causal and not cfg.sliding_window
                      and mode != "off"
                      and (mode == "interpret"
                           or jax.default_backend() == "tpu"))
        # S == 1 is the decode hot loop (fused decode kernels); S > 1 is a
        # prefill or chunk continuing the cache (ragged flash kernel).
        use_fused = kernels_on and s == 1
        use_flash = kernels_on and s > 1
        if paged is not None and pages is not None:
            # Paged cache: ck/cv are the layer's physical page pools
            # (num_pages, page_size, Hkv, dh) shared by every slot; the
            # (B, max_pages) page table maps each slot's logical page to
            # its pool row.  Writes scatter through the table (masked
            # rows aimed at num_pages and dropped), reads either gather
            # the slot's pages back into a contiguous view (jnp
            # reference) or ride the table into the fused kernel as a
            # second scalar-prefetch vector.  SWA is gated off (the
            # ring-buffer layout stays contiguous-only).
            psz, mp, npg = paged.page_size, paged.max_pages, paged.num_pages
            page_idx = t_abs // psz                                # (B, S)
            row = t_abs % psz
            page_id = jnp.take_along_axis(
                pages, jnp.clip(page_idx, 0, mp - 1), axis=1)      # (B, S)
            ok_w = act2d & (page_idx < mp) & (page_id >= 0)
            page_w = jnp.where(ok_w, page_id, npg)     # OOB sentinel: drop
            if quantized:
                ck = ck.at[page_w, row].set(kq_w, mode="drop")
                cv = cv.at[page_w, row].set(vq_w, mode="drop")
                cks = cks.at[page_w, row].set(ks_w, mode="drop")
                cvs = cvs.at[page_w, row].set(vs_w, mode="drop")
            else:
                ck = ck.at[page_w, row].set(k.astype(ck.dtype), mode="drop")
                cv = cv.at[page_w, row].set(v.astype(cv.dtype), mode="drop")
            if use_fused and quantized:
                from repro.kernels.attention.decode_int8 import \
                    paged_quantized_gqa_decode_attention
                out = paged_quantized_gqa_decode_attention(
                    q[:, 0], ck, cks, cv, cvs, pages, length=new_len,
                    scale=scale, interpret=(mode == "interpret"))[:, None]
            elif use_fused:
                from repro.kernels.attention.decode import \
                    paged_gqa_decode_attention
                out = paged_gqa_decode_attention(
                    q[:, 0], ck, cv, pages, length=new_len, scale=scale,
                    interpret=(mode == "interpret"))[:, None]
            else:
                safe = jnp.clip(pages, 0, npg - 1)
                kg = ck[safe].reshape(b, mp * psz, cfg.num_kv_heads,
                                      cfg.head_dim)
                vg = cv[safe].reshape(b, mp * psz, cfg.num_kv_heads,
                                      cfg.head_dim)
                if quantized:
                    kg = quantize.dequantize_rows(
                        kg, cks[safe].reshape(b, mp * psz,
                                              cfg.num_kv_heads))
                    vg = quantize.dequantize_rows(
                        vg, cvs[safe].reshape(b, mp * psz,
                                              cfg.num_kv_heads))
                if use_flash:
                    out = _chunk_attention(q, kg, vg, lengths, new_len,
                                           interpret=(mode == "interpret"))
                else:
                    k_pos = jnp.arange(mp * psz, dtype=jnp.int32)
                    k_valid = k_pos[None, :] < new_len[:, None]
                    out = attention_core(q, kg, vg, pos_b, k_pos,
                                         causal=cfg.causal, window=None,
                                         scale=scale, k_valid=k_valid)
            new_cache = {"k": ck, "v": cv}
            if quantized:
                new_cache.update({"k_scale": cks, "v_scale": cvs})
            out = out.reshape(b, s, cfg.q_dim).astype(x.dtype)
            y = out @ gather_weight(params["wo"]).astype(x.dtype)
            return constrain(y, "batch", "res_seq", "embed"), new_cache
        cache_len = ck.shape[1]
        b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]            # (B, 1)
        t_write = t_abs % cache_len if cfg.sliding_window else t_abs
        # Inactive slots must not write: aim their rows out of bounds and
        # let mode="drop" discard them (also guards depth overflow).
        t_write = jnp.where(act2d, t_write, cache_len)
        if quantized:
            ck = ck.at[b_idx, t_write].set(kq_w, mode="drop")
            cv = cv.at[b_idx, t_write].set(vq_w, mode="drop")
            cks = cks.at[b_idx, t_write].set(ks_w, mode="drop")
            cvs = cvs.at[b_idx, t_write].set(vs_w, mode="drop")
        else:
            ck = ck.at[b_idx, t_write].set(k.astype(ck.dtype), mode="drop")
            cv = cv.at[b_idx, t_write].set(v.astype(cv.dtype), mode="drop")
        ck = constrain(ck, "batch", "kv_seq", "kv_heads", None)
        cv = constrain(cv, "batch", "kv_seq", "kv_heads", None)
        k_slots = jnp.arange(cache_len, dtype=jnp.int32)
        if cfg.sliding_window:
            # Ring buffer, per slot: ring slot j holds absolute position
            # end - ((end % L - j) % L) where end is the slot's newest
            # written position.
            end = new_len - 1                                      # (B,)
            k_pos = (end[:, None]
                     - ((end[:, None] % cache_len - k_slots[None, :])
                        % cache_len))                              # (B, L)
            k_valid = (k_pos >= 0) & (k_pos < new_len[:, None])
        else:
            k_pos = k_slots                                        # (L,)
            k_valid = k_slots[None, :] < new_len[:, None]          # (B, L)
        if use_fused:
            # Serving decode: the single-token hot loop goes through the
            # registry's fused autotuned decode kernel (plan resolved at
            # trace time against the cache `plan_for_model` pre-warmed;
            # the per-slot valid prefixes ride the scalar-prefetch vector
            # the kernel skips on — each slot streams only its own
            # blocks).  The ring-buffer SWA layout and training stay on
            # the jnp path below.  $REPRO_DECODE_KERNEL: "auto" (TPU
            # only), "interpret" (force interpret mode — CPU
            # tests/demos), "off"; resolved at trace time, so changing it
            # after the serve step is jitted requires a retrace (new
            # process / cache clear).
            from repro.kernels.autotune import dispatch
            if quantized:
                out = dispatch("decode_int8", q[:, 0], ck, cks, cv, cvs,
                               length=new_len,
                               interpret=(mode == "interpret"))[:, None]
            else:
                out = dispatch("decode", q[:, 0], ck, cv, length=new_len,
                               interpret=(mode == "interpret"))[:, None]
        else:
            kr, vr = ck, cv
            if quantized:
                kr = quantize.dequantize_rows(ck, cks)
                vr = quantize.dequantize_rows(cv, cvs)
            if use_flash:
                out = _chunk_attention(q, kr, vr, lengths, new_len,
                                       interpret=(mode == "interpret"))
            else:
                out = attention_core(q, kr, vr, pos_b, k_pos,
                                     causal=cfg.causal,
                                     window=cfg.sliding_window, scale=scale,
                                     k_valid=k_valid)
        new_cache = {"k": ck, "v": cv}
        if quantized:
            new_cache.update({"k_scale": cks, "v_scale": cvs})

    out = out.reshape(b, s, cfg.q_dim).astype(x.dtype)
    y = out @ gather_weight(params["wo"]).astype(x.dtype)
    return constrain(y, "batch", "res_seq", "embed"), new_cache


def _chunk_attention(q, k, v, lengths, new_len, *, interpret: bool):
    """Prefill / chunked prefill through the cache on the registry's flash
    kernel: slot b's S queries sit at ``lengths[b] + i`` and see the first
    ``new_len[b]`` rows of its (just written) cache view ``k``/``v``
    (B, L, Hkv, dh), causally.  q is upcast to the cache dtype, as the
    decode kernels do."""
    from repro.kernels.autotune import dispatch
    return dispatch("attention", q.astype(k.dtype), k, v, causal=True,
                    q_offset=lengths, kv_len=new_len, interpret=interpret)


def attention_cache_init(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16,
                         paged=None) -> Params:
    quantized = jnp.dtype(dtype) == jnp.int8
    if paged is not None:
        # Paged layout: a pool of physical pages shared by every slot
        # (the per-slot page table lives once at the cache root, not per
        # layer — page id p is pool row p in every layer's K and V).
        if cfg.sliding_window:
            raise ValueError(
                "paged KV cache does not support sliding-window attention "
                "(the ring-buffer layout is contiguous-only)")
        shape = (paged.num_pages, paged.page_size, cfg.num_kv_heads,
                 cfg.head_dim)
    else:
        if cfg.sliding_window:
            cache_len = min(cache_len, cfg.sliding_window)
        shape = (batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
    if quantized:
        # Int8 layout: q values + a parallel f32 per-token-row scale leaf
        # per KV head (one scale for each written (dh,) vector — see
        # runtime/quantize.py for why the block is a row, not a page).
        kq, ks = quantize.quantized_zeros(shape)
        vq, vs = quantize.quantized_zeros(shape)
        return {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_init(key, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], (d_model, d_ff), dtype=dtype),
        "w_up": _dense_init(ks[1], (d_model, d_ff), dtype=dtype),
        "w_down": _dense_init(ks[2], (d_ff, d_model), dtype=dtype),
    }


def swiglu_param_specs() -> Params:
    return {
        "w_gate": ("embed", "ff"),
        "w_up": ("embed", "ff"),
        "w_down": ("ff", "embed"),
    }


def swiglu_apply(params: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ params["w_gate"].astype(x.dtype)) * (
        x @ params["w_up"].astype(x.dtype)
    )
    h = constrain(h, "batch", "seq", "ff")
    return constrain(h @ params["w_down"].astype(x.dtype), "batch", "res_seq", "embed")


def gelu_mlp_init(key, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "w_up": _dense_init(ks[0], (d_model, d_ff), dtype=dtype),
        "w_down": _dense_init(ks[1], (d_ff, d_model), dtype=dtype),
    }


def gelu_mlp_param_specs() -> Params:
    return {"w_up": ("embed", "ff"), "w_down": ("ff", "embed")}


def gelu_mlp_apply(params: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.gelu(x @ params["w_up"].astype(x.dtype))
    h = constrain(h, "batch", "seq", "ff")
    return constrain(h @ params["w_down"].astype(x.dtype), "batch", "res_seq", "embed")


# ---------------------------------------------------------------------------
# Embeddings / heads
# ---------------------------------------------------------------------------

def embedding_init(key, vocab: int, d_model: int, dtype=jnp.float32) -> Params:
    return {"table": _dense_init(key, (vocab, d_model), dtype=dtype)}


def embedding_lookup(params: Params, tokens: jax.Array) -> jax.Array:
    out = jnp.take(params["table"], tokens, axis=0)
    return constrain(out, "batch", "res_seq", "embed")


def unembed(params: Params, x: jax.Array) -> jax.Array:
    """Logits (vocab-sharded; never gathered — the loss is sharded too)."""
    logits = x @ params["table"].T.astype(x.dtype)
    return constrain(logits, "batch", "seq", "vocab")
