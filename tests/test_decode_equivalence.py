"""Decode (KV cache / recurrent state) must reproduce teacher-forced
training logits exactly — covers RoPE offsets, SWA ring buffer, Mamba conv
tails, RWKV token shifts, and hybrid stacking."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer
from repro.models.config import ModelConfig

KEY = jax.random.PRNGKey(7)


def _tiny(family, **kw):
    base = dict(name=f"tiny-{family}", family=family, num_layers=4,
                d_model=64, d_ff=128, vocab_size=97, num_heads=4,
                num_kv_heads=2)
    base.update(kw)
    return ModelConfig(**base)


CASES = {
    "dense": _tiny("dense", qk_norm=True),
    "dense_bias": _tiny("dense", qkv_bias=True),
    "swa_ring": _tiny("dense", sliding_window=6),
    "rwkv": _tiny("ssm", num_heads=0, num_kv_heads=0, rwkv_head_dim=16,
                  rwkv_lora_dim=8),
    "jamba": _tiny("hybrid", num_layers=8, attn_period=4, attn_offset=2,
                   num_experts=4, top_k=2, moe_d_ff=32, moe_every=2,
                   moe_offset=1, ssm_state=4, ssm_conv=3,
                   capacity_factor=8.0),
}


@pytest.mark.parametrize("name", list(CASES))
def test_decode_matches_teacher_forcing(name):
    cfg = CASES[name]
    b, s = 2, 12
    params = transformer.init(cfg, KEY)
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    full, _, _ = transformer.forward(cfg, params, {"tokens": toks},
                                     compute_dtype=jnp.float32)
    cache = transformer.cache_init(cfg, b, s, dtype=jnp.float32)
    outs = []
    for t in range(s):
        lg, cache, _ = transformer.forward(
            cfg, params, {"tokens": toks[:, t:t + 1]}, cache=cache,
            compute_dtype=jnp.float32)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("name", ["dense", "dense_bias"])
def test_decode_through_fused_kernel_matches_teacher_forcing(
        name, monkeypatch, tmp_path):
    """The serving decode hot loop routed through the fused autotuned
    decode-attention kernel (REPRO_DECODE_KERNEL=interpret forces the TPU
    path in interpret mode) must still reproduce teacher-forced logits."""
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "interpret")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    cfg = CASES[name]
    b, s = 2, 8
    params = transformer.init(cfg, KEY)
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    full, _, _ = transformer.forward(cfg, params, {"tokens": toks},
                                     compute_dtype=jnp.float32)
    cache = transformer.cache_init(cfg, b, s, dtype=jnp.float32)
    outs = []
    for t in range(s):
        lg, cache, _ = transformer.forward(
            cfg, params, {"tokens": toks[:, t:t + 1]}, cache=cache,
            compute_dtype=jnp.float32)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.int8])
def test_chunked_prefill_through_flash_kernel_matches_teacher_forcing(
        kv_dtype, monkeypatch, tmp_path):
    """The serve step's S > 1 path: two ragged prompts prefilled in one
    chunk through the ragged flash kernel, then a second chunk continuing
    both caches from their own depths, then one fused decode step — each
    slot's logits must equal the teacher-forced forward of its sequence."""
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "interpret")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    cfg = CASES["dense"]
    params = transformer.init(cfg, KEY)
    lens = np.array([10, 6])
    toks = np.asarray(jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size))
    cache = transformer.cache_init(cfg, 2, 16, dtype=kv_dtype)
    got = {0: [], 1: []}

    def step(chunk, active):
        nonlocal cache
        lg, cache, _ = transformer.forward(
            cfg, params, {"tokens": jnp.asarray(chunk)}, cache=cache,
            active=jnp.asarray(active), compute_dtype=jnp.float32)
        return np.asarray(lg)

    lg = step(toks[:, :10], np.arange(10)[None] < lens[:, None])
    for s_ in range(2):
        got[s_] += list(lg[s_, :lens[s_]])
    # second chunk: 3 more tokens each, from each slot's own depth
    chunk = np.stack([toks[s_, lens[s_]:lens[s_] + 3] for s_ in range(2)])
    lg = step(chunk, np.ones((2, 3), bool))
    for s_ in range(2):
        got[s_] += list(lg[s_])
    nxt = np.stack([toks[s_, lens[s_] + 3:lens[s_] + 4] for s_ in range(2)])
    lg = step(nxt, np.ones((2,), bool))
    for s_ in range(2):
        got[s_] += list(lg[s_])
    tol = 2e-3 if kv_dtype == jnp.float32 else 5e-2
    for s_ in range(2):
        n = lens[s_] + 4
        full, _, _ = transformer.forward(
            cfg, params, {"tokens": jnp.asarray(toks[s_:s_ + 1, :n])},
            compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.stack(got[s_]), np.asarray(full[0]),
                                   rtol=tol, atol=tol)


def test_swa_ring_buffer_bounded_cache():
    cfg = CASES["swa_ring"]
    cache = transformer.cache_init(cfg, 1, 1000, dtype=jnp.float32)
    k = jax.tree.leaves(cache["blocks"])[0]
    # cache length is clamped to the window, not the full 1000
    assert cfg.sliding_window in k.shape
