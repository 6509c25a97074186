"""The plain float32 reference of the served models.

A decoder of the published Qwen2.5 / Qwen3 equations in straightforward
``jax.numpy``, written from the published description and importing
nothing of the program: token embedding; per layer, RMSNorm, Q/K/V
projections (with their biases where the configuration has ``qkv_bias``),
per-head RMSNorm of Q and K before RoPE where it has ``qk_norm``, rotary
embedding over the two halves of each head (inverse frequencies
``theta ** (-2i / head_dim)``), causal grouped-query attention, the output
projection and a residual add, then RMSNorm, the SwiGLU MLP and a residual
add; the final RMSNorm and the untied head.  Every matmul runs at
``Precision.HIGHEST`` in float32, with no cache and no batching.

It runs one sequence at a time, layer by layer, with attention in blocks
of query rows, so that a 8k-token sequence fits beside nothing else on one
chip.  Sequences are padded at the end to a multiple of ``PAD``; causal
attention keeps the padding out of every real row.

``quant`` names the lower-precision control, one step below the bf16 the
configurations state: every linear layer (the head included) multiplies
``"int8"`` or ``"fp8"`` (e4m3) values, weights scaled per output channel
and activations per token row, and K and V are rounded the same way per
token row and head, as such a serving path would.  The values are
simulated in float32; only their precision changes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PAD = 512          # sequence padding quantum (bounds the number of compiles)
Q_BLOCK = 256      # query rows per attention block
HEAD_BLOCK = 128   # positions per block of the head


QMAX = {"int8": 127.0, "fp8": 448.0}


def _q(x, axis, quant):
    """Round to ``quant`` with one scale per slice along ``axis``."""
    if not quant:
        return x
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / QMAX[quant]
    s = jnp.where(s == 0, 1.0, s)
    if quant == "int8":
        return jnp.round(x / s) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (T, in) @ w (in, out) in float32 (``quant`` values if set)."""
    return jnp.matmul(_q(x, -1, quant), _q(w, 0, quant), precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x (T, H, dh); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv[None]         # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def _layer(w, x, *, spec, quant):
    sp = dict(spec)
    t = x.shape[0]
    hq, hkv, dh = sp["num_attention_heads"], sp["num_key_value_heads"], \
        sp["head_dim"]
    eps = sp["rms_norm_eps"]
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(t, dtype=jnp.int32)

    h = _rms(x, f32["input_layernorm"], eps)
    q = _mm(h, f32["q_proj"], quant)
    k = _mm(h, f32["k_proj"], quant)
    v = _mm(h, f32["v_proj"], quant)
    if sp.get("qkv_bias"):
        q, k, v = (q + f32["q_proj_bias"], k + f32["k_proj_bias"],
                   v + f32["v_proj_bias"])
    q, k, v = (q.reshape(t, hq, dh), k.reshape(t, hkv, dh),
               v.reshape(t, hkv, dh))
    if sp.get("qk_norm"):
        q = _rms(q, f32["q_norm"], eps)
        k = _rms(k, f32["k_norm"], eps)
    q = _rope(q, pos, sp["rope_theta"])
    k = _rope(k, pos, sp["rope_theta"])
    k, v = _q(k, -1, quant), _q(v, -1, quant)
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1)                                # (T, Hq, dh)
    vv = jnp.repeat(v, g, axis=1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, kk, precision=HI) / math.sqrt(dh)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision=HI)

    att = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    att = att.reshape(t, hq * dh)
    x = x + _mm(att, f32["o_proj"], quant)
    h = _rms(x, f32["post_attention_layernorm"], eps)
    gate = _mm(h, f32["gate_proj"], quant)
    up = _mm(h, f32["up_proj"], quant)
    return x + _mm(jax.nn.silu(gate) * up, f32["down_proj"], quant)


@functools.partial(jax.jit, static_argnames=("spec", "count", "quant"))
def _head(norm, head, x, start, *, spec, count, quant):
    sp = dict(spec)
    rows = jax.lax.dynamic_slice_in_dim(x, start, count, 0)
    rows = _rms(rows, norm.astype(jnp.float32), sp["rms_norm_eps"])
    table = _q(head.astype(jnp.float32), 1, quant)

    def block(r):
        return jnp.matmul(_q(r, -1, quant), table.T, precision=HI)
    return jax.lax.map(block, rows.reshape(-1, HEAD_BLOCK, rows.shape[-1])
                       ).reshape(count, -1)


LAYER_NAMES = ("input_layernorm", "post_attention_layernorm", "q_proj",
               "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
               "down_proj", "q_proj_bias", "k_proj_bias", "v_proj_bias",
               "q_norm", "k_norm")

SPEC_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
             "rms_norm_eps", "rope_theta", "qkv_bias", "qk_norm")


def frozen(spec: dict) -> tuple:
    return tuple((k, spec.get(k)) for k in SPEC_KEYS)


def logits(weights: dict, spec: dict, tokens, start: int, count: int, *,
           quant: str | None = None) -> np.ndarray:
    """float32 logits at positions ``start .. start + count - 1`` of the
    sequence ``tokens`` (each row predicts the token after it)."""
    tokens = np.asarray(tokens, np.int32)
    t = tokens.size
    cp = -(-count // HEAD_BLOCK) * HEAD_BLOCK
    tp = -(-max(t, start + cp) // PAD) * PAD
    padded = np.zeros(tp, np.int32)
    padded[:t] = tokens
    x = jnp.take(weights["embed_tokens"], jnp.asarray(padded),
                 axis=0).astype(jnp.float32)
    fs = frozen(spec)
    for l in range(spec["num_hidden_layers"]):
        wl = {n: weights[n][l] for n in LAYER_NAMES if n in weights}
        x = _layer(wl, x, spec=fs, quant=quant)
    out = _head(weights["norm"], weights["lm_head"], x, start,
                spec=fs, count=cp, quant=quant)
    return np.asarray(out[:count])


def served_gap(weights: dict, spec: dict, prompt, served, *,
               controls=()) -> dict:
    """How far below the reference's best logit each served token lies.

    ``served`` are the tokens the system emitted for ``prompt``, first
    one included; position ``len(prompt) - 1 + j`` predicts ``served[j]``.
    Returns the widest gap of the served tokens and, for each control
    precision in ``controls``, the widest gap of the tokens that control
    would put first at the same positions."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    start, n = prompt.size - 1, served.size
    ref = logits(weights, spec, seq, start, n)
    best = ref.max(axis=1)
    out = {"gap": float((best - ref[np.arange(n), served]).max()),
           "tokens": int(n)}
    for quant in controls:
        pick = logits(weights, spec, seq, start, n, quant=quant).argmax(1)
        out[f"{quant}_gap"] = float((best - ref[np.arange(n), pick]).max())
    return out
