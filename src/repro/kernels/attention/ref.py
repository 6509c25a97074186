"""Pure-jnp oracle for flash attention (materialized-logits softmax)."""

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, scale, causal=True, window=None,
                  q_offset=None, kv_len=None):
    """q: (BH, Sq, dh); k, v: (BH, Sk, dh).  ``q_offset``/``kv_len``
    ((BH,) int, optional — `kernel.ragged_flash_attention`'s operands):
    row r's queries sit at ``q_offset[r] + i`` and only its first
    ``kv_len[r]`` keys are valid."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    sq, sk = s.shape[1], s.shape[2]
    q_pos = jnp.arange(sq)[None, :, None]
    k_pos = jnp.arange(sk)[None, None, :]
    if q_offset is not None:
        q_pos = q_pos + jnp.asarray(q_offset)[:, None, None]
    ok = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if causal:
        ok &= q_pos >= k_pos
    if window is not None:
        ok &= (q_pos - k_pos) < window
    if kv_len is not None:
        ok &= k_pos < jnp.asarray(kv_len)[:, None, None]
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # A q row with zero surviving keys (sq > sk with a window, or a row
    # that sees no valid key) outputs 0, matching the kernel's l-floor
    # convention — not the uniform-softmax mean a raw softmax over -1e30
    # logits yields.
    p = p * ok.any(axis=-1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
