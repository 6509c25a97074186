"""Mamba-style selective SSM block — the SSM half of the Jamba hybrid.

Train path scans the selective recurrence over the sequence; decode carries an
O(1) state (conv tail + SSM hidden), which is what makes `long_500k` decoding
sub-quadratic for hybrid/ssm architectures.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.parallel.sharding import constrain

Params = dict


def mamba_init(key, cfg, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    n = cfg.ssm_state
    r = cfg.ssm_dt_rank
    ks = jax.random.split(key, 6)
    a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None, :], (d_in, 1))
    return {
        "in_proj": layers._dense_init(ks[0], (d, 2 * d_in), dtype=dtype),
        "conv_w": layers._dense_init(ks[1], (cfg.ssm_conv, d_in), scale=0.1, dtype=dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "x_proj": layers._dense_init(ks[2], (d_in, r + 2 * n), dtype=dtype),
        # dt_proj and dt_bias are read in f32 (the delta path), so kept f32
        "dt_proj": layers._dense_init(ks[3], (r, d_in), scale=r**-0.5),
        "dt_bias": jnp.full((d_in,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "A_log": jnp.log(a),                           # kept f32
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": layers._dense_init(ks[4], (d_in, d), dtype=dtype),
    }


def mamba_param_specs(cfg) -> Params:
    return {
        "in_proj": ("embed", "ff"),
        "conv_w": (None, "ff"),
        "conv_b": ("ff",),
        "x_proj": ("ff", None),
        "dt_proj": (None, "ff"),
        "dt_bias": ("ff",),
        "A_log": ("ff", None),
        "D": ("ff",),
        "out_proj": ("ff", "embed"),
    }


def _causal_depthwise_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                           tail: jax.Array | None = None):
    """x: (B,S,C); w: (W,C) depthwise causal conv.  tail: (B,W-1,C) history."""
    width = w.shape[0]
    tail_dtype = x.dtype if tail is None else tail.dtype
    if tail is None:
        tail = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # (B, S+W-1, C)
    out = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(width)
    )
    new_tail = (xp[:, -(width - 1):, :].astype(tail_dtype)
                if width > 1 else tail)
    return out + b[None, None, :], new_tail


def _selective_scan(delta, a, b_ssm, c_ssm, x, h0):
    """delta,x: (B,S,Din); a: (Din,N); b_ssm,c_ssm: (B,S,N); h0: (B,Din,N)."""

    def step(h, inp):
        d_t, b_t, c_t, x_t = inp                       # (B,Din),(B,N),(B,N),(B,Din)
        da = jnp.exp(d_t[..., None] * a[None])         # (B,Din,N)
        dbx = d_t[..., None] * b_t[:, None, :] * x_t[..., None]
        h = da * h + dbx
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    xs = (
        jnp.moveaxis(delta, 1, 0),
        jnp.moveaxis(b_ssm, 1, 0),
        jnp.moveaxis(c_ssm, 1, 0),
        jnp.moveaxis(x, 1, 0),
    )
    h_last, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h_last              # (B,S,Din), (B,Din,N)


def mamba_apply(params: Params, x: jax.Array, cfg,
                cache: Params | None = None):
    """x: (B,S,D) -> (B,S,D).  cache: {"conv": (B,W-1,Din), "h": (B,Din,N)}."""
    b, s, d = x.shape
    d_in = cfg.ssm_expand * d
    n = cfg.ssm_state
    r = cfg.ssm_dt_rank

    xz = x @ params["in_proj"].astype(x.dtype)         # (B,S,2*Din)
    xb, z = jnp.split(xz, 2, axis=-1)
    xb = constrain(xb, "batch", "seq", "ff")

    tail = cache["conv"] if cache is not None else None
    xb, new_tail = _causal_depthwise_conv(
        xb, params["conv_w"].astype(x.dtype), params["conv_b"].astype(x.dtype), tail)
    xb = jax.nn.silu(xb)

    dbl = (xb @ params["x_proj"].astype(x.dtype)).astype(jnp.float32)
    dt, b_ssm, c_ssm = jnp.split(dbl, [r, r + n], axis=-1)
    delta = jax.nn.softplus(
        dt @ params["dt_proj"].astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["A_log"])

    h0 = (cache["h"] if cache is not None
          else jnp.zeros((b, d_in, n), jnp.float32))
    # delta stays f32 (exp stability); the B/C/x streams are bf16 — halves
    # the dominant activation traffic of the scan (§Perf iteration J2).
    y, h_last = _selective_scan(delta, a, b_ssm.astype(jnp.bfloat16),
                                c_ssm.astype(jnp.bfloat16),
                                xb.astype(jnp.bfloat16), h0)
    y = (y + params["D"][None, None, :] * xb.astype(jnp.float32)).astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = y @ params["out_proj"].astype(x.dtype)
    new_cache = {"conv": new_tail, "h": h_last} if cache is not None else None
    return constrain(out, "batch", "res_seq", "embed"), new_cache


def mamba_cache_init(cfg, batch: int, dtype=jnp.bfloat16) -> Params:
    d_in = cfg.ssm_expand * cfg.d_model
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, d_in), dtype),
        "h": jnp.zeros((batch, d_in, cfg.ssm_state), jnp.float32),
    }
