"""Public flash-attention wrapper with GQA folding and backend dispatch."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.attention import kernel, ref


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret", "use_kernel"))
def mha_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int | None = None,
                  block_q: int = 512, block_k: int = 512,
                  interpret: bool = False, use_kernel: bool | None = None,
                  q_offset: jax.Array | None = None,
                  kv_len: jax.Array | None = None):
    """q: (B, Sq, Hq, dh); k, v: (B, Sk, Hkv, dh) -> (B, Sq, Hq, dh).

    ``q_offset``/``kv_len`` ((B,) int32, given together, causal only):
    the queries continue a KV cache — slot b's query i sits at position
    ``q_offset[b] + i`` and sees the first ``kv_len[b]`` rows of k/v
    (`kernel.ragged_flash_attention`)."""
    b, sq, hq, dh = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(dh)
    if use_kernel is None:
        use_kernel = interpret or jax.default_backend() == "tpu"

    # Fold heads into batch; repeat KV across the GQA group.  q head j
    # reads KV head j // g (the model's grouping, `layers.attention_core`),
    # so each KV head repeats g times in place along the head axis.
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * hq, sk, dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * hq, sk, dh)

    if q_offset is not None:
        if not causal or window is not None:
            raise ValueError("a cache-continuing chunk is causal and "
                             "windowless")
        off = jnp.repeat(jnp.asarray(q_offset, jnp.int32), hq)
        lens = jnp.repeat(jnp.asarray(kv_len, jnp.int32), hq)
        if use_kernel:
            out = kernel.ragged_flash_attention(
                qf, kf, vf, off, lens, scale=scale, block_q=block_q,
                block_k=block_k, interpret=interpret)
        else:
            out = ref.attention_ref(qf, kf, vf, scale=scale, q_offset=off,
                                    kv_len=lens)
    elif use_kernel:
        out = kernel.flash_attention(
            qf, kf, vf, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, interpret=interpret)
    else:
        out = ref.attention_ref(qf, kf, vf, scale=scale, causal=causal,
                                window=window)
    return out.reshape(b, hq, sq, dh).transpose(0, 2, 1, 3)
