"""RWKV-6 ("Finch") block: attention-free time mix with data-dependent decay.

The WKV recurrence keeps a per-head (dh x dh) state, so decode is O(1) in
sequence length — `long_500k` costs the same per token as short contexts.

Faithful structure: token-shift interpolation (static mix vectors), a
low-rank data-dependent decay `w_t = exp(-exp(w0 + tanh(x W_a) W_b))`
(the defining Finch feature), bonus `u`, per-head normalization, gated
output, and squared-ReLU channel mix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.parallel.sharding import constrain

Params = dict


def rwkv_time_init(key, cfg, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    l = cfg.rwkv_lora_dim
    ks = jax.random.split(key, 7)
    h = d // cfg.rwkv_head_dim
    return {
        "mix": 0.5 * jnp.ones((5, d), dtype),          # r,k,v,g,w shift mixes
        "wr": layers._dense_init(ks[0], (d, d), dtype=dtype),
        "wk": layers._dense_init(ks[1], (d, d), dtype=dtype),
        "wv": layers._dense_init(ks[2], (d, d), dtype=dtype),
        "wg": layers._dense_init(ks[3], (d, d), dtype=dtype),
        "wo": layers._dense_init(ks[4], (d, d), dtype=dtype),
        "decay_w0": jnp.full((d,), -2.0, jnp.float32),
        "decay_a": layers._dense_init(ks[5], (d, l), dtype=jnp.float32),
        "decay_b": layers._dense_init(ks[6], (l, d), dtype=jnp.float32),
        "bonus_u": jnp.zeros((h, cfg.rwkv_head_dim), jnp.float32),
        "ln_x": layers.rmsnorm_init(d),
    }


def rwkv_channel_init(key, cfg, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "cmix": 0.5 * jnp.ones((2, d), dtype),         # r,k shift mixes
        "ck": layers._dense_init(ks[0], (d, cfg.d_ff), dtype=dtype),
        "cv": layers._dense_init(ks[1], (cfg.d_ff, d), dtype=dtype),
        "cr": layers._dense_init(ks[2], (d, d), dtype=dtype),
    }


def rwkv_time_param_specs(cfg) -> Params:
    return {
        "mix": (None, "embed"),
        "wr": ("embed", "heads"), "wk": ("embed", "heads"),
        "wv": ("embed", "heads"), "wg": ("embed", "heads"),
        "wo": ("heads", "embed"),
        "decay_w0": ("embed",),
        "decay_a": ("embed", None), "decay_b": (None, "embed"),
        "bonus_u": ("heads", None),
        "ln_x": {"scale": (None,)},
    }


def rwkv_channel_param_specs(cfg) -> Params:
    return {
        "cmix": (None, "embed"),
        "ck": ("embed", "ff"), "cv": ("ff", "embed"), "cr": ("embed", "embed"),
    }


def _token_shift(x: jax.Array, prev: jax.Array | None):
    """Shifted-by-one sequence; ``prev`` is the last token of the previous
    chunk (decode state), zeros at the very start."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    shifted = jnp.concatenate([prev.astype(x.dtype), x[:, :-1]], axis=1)
    return shifted, x[:, -1:].astype(prev.dtype)


def _wkv_scan(r, k, v, w, u, s0):
    """Recurrence per head.  r,k,v: (B,S,H,dh); w: (B,S,H,dh) decay in (0,1);
    u: (H,dh) bonus; s0: (B,H,dh,dh) state (k-dim x v-dim)."""

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp                       # (B,H,dh)
        kv = k_t[..., :, None] * v_t[..., None, :]     # (B,H,dh,dh)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[None, :, :, None] * kv)
        s = w_t[..., :, None] * s + kv
        return s, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    s_last, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1), s_last              # (B,S,H,dh)


def rwkv_time_mix(params: Params, x: jax.Array, cfg,
                  state: Params | None = None):
    b, s, d = x.shape
    h, dh = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    prev = state["shift_t"] if state is not None else None
    shifted, last = _token_shift(x, prev)
    mix = params["mix"].astype(x.dtype)
    xr, xk, xv, xg, xw = (x + (shifted - x) * mix[i] for i in range(5))

    r = (xr @ params["wr"].astype(x.dtype)).reshape(b, s, h, dh)
    k = (xk @ params["wk"].astype(x.dtype)).reshape(b, s, h, dh)
    v = (xv @ params["wv"].astype(x.dtype)).reshape(b, s, h, dh)
    g = jax.nn.silu(xg @ params["wg"].astype(x.dtype))

    # Data-dependent decay (the RWKV6 novelty).
    dd = jnp.tanh(xw.astype(jnp.float32) @ params["decay_a"]) @ params["decay_b"]
    w = jnp.exp(-jnp.exp(params["decay_w0"][None, None] + dd))  # (B,S,D)
    w = w.reshape(b, s, h, dh)

    s0 = (state["wkv"] if state is not None
          else jnp.zeros((b, h, dh, dh), jnp.float32))
    y, s_last = _wkv_scan(r.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), w, params["bonus_u"], s0)
    y = layers.rmsnorm(params["ln_x"], y.reshape(b, s, d), cfg.norm_eps)
    out = (y.astype(x.dtype) * g) @ params["wo"].astype(x.dtype)
    new_state = None
    if state is not None:
        new_state = {"shift_t": last, "wkv": s_last}
    return constrain(out, "batch", "res_seq", "embed"), new_state


def rwkv_channel_mix(params: Params, x: jax.Array, cfg,
                     state: Params | None = None):
    prev = state["shift_c"] if state is not None else None
    shifted, last = _token_shift(x, prev)
    cmix = params["cmix"].astype(x.dtype)
    xk = x + (shifted - x) * cmix[0]
    xr = x + (shifted - x) * cmix[1]
    kk = jnp.square(jax.nn.relu(xk @ params["ck"].astype(x.dtype)))
    kk = constrain(kk, "batch", "seq", "ff")
    out = jax.nn.sigmoid(xr @ params["cr"].astype(x.dtype)) * (
        kk @ params["cv"].astype(x.dtype))
    new_state = {"shift_c": last} if state is not None else None
    return constrain(out, "batch", "res_seq", "embed"), new_state


def rwkv_cache_init(cfg, batch: int, dtype=jnp.bfloat16) -> Params:
    d = cfg.d_model
    h, dh = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    return {
        "shift_t": jnp.zeros((batch, 1, d), dtype),
        "wkv": jnp.zeros((batch, h, dh, dh), jnp.float32),
        "shift_c": jnp.zeros((batch, 1, d), dtype),
    }
