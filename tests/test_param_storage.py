"""Parameters stored in the compute dtype run the f32-stored arithmetic.

`transformer.init` stores a leaf in the requested dtype where `forward`
casts it to the activations' dtype, and in f32 where it reads it in f32.
A tree stored that way in bf16 must give bitwise the logits, and so the
greedy tokens, of the f32 tree it was rounded from, in every served
family; and the `Server` must ask for that tree in the one call shape the
benchmark harness substitutes its seeded weights into."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.launch.serve import Server
from repro.models import transformer

KEY = jax.random.PRNGKey(3)

# One smoke config per served family the storage rule has to hold for.
FAMILIES = {
    "dense_qk_norm": "qwen3_14b",
    "dense_qkv_bias": "qwen2_5_32b",
    "moe": "phi3_5_moe_42b",
    "mamba_hybrid": "jamba_1_5_large_398b",
    "rwkv": "rwkv6_7b",
}

# Leaves `forward` reads in f32: they stay f32 at any storage dtype.
F32_LEAVES = {"scale", "router", "dt_proj", "dt_bias", "A_log", "D",
              "decay_w0", "decay_a", "decay_b", "bonus_u"}


def _leaf_path(path) -> list:
    return [str(getattr(k, "key", k)) for k in path]


def _trees(cfg):
    """An f32 tree with no value exactly a bf16 value, and the same tree
    stored under the rule at bf16.  Vectors are drawn N(0, 1) and
    matrices N(0, 1/fan-in), so every path (the SSM's delta and scan
    included) carries signal and rounding an f32 leaf to bf16 would move
    the logits."""
    f32 = transformer.init(cfg, KEY, dtype=jnp.float32)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(f32)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    drawn = []
    for (path, a), k in zip(leaves, keys):
        shape = a.shape[1:] if _leaf_path(path)[0] == "blocks" else a.shape
        std = 1.0 if len(shape) == 1 else shape[-2] ** -0.5
        drawn.append(std * jax.random.normal(k, a.shape, jnp.float32))
    f32 = treedef.unflatten(drawn)
    stored = jax.eval_shape(
        lambda: transformer.init(cfg, KEY, dtype=jnp.bfloat16))
    return f32, jax.tree.map(lambda a, s: a.astype(s.dtype), f32, stored)


def _greedy(cfg, params, prompt, steps):
    """Prefill, then ``steps`` greedy decode steps: every step's logits."""
    b = prompt.shape[0]
    fwd = jax.jit(lambda p, c, t: transformer.forward(
        cfg, p, {"tokens": t}, cache=c)[:2])
    cache = transformer.cache_init(cfg, b, prompt.shape[1] + steps)
    logits, cache = fwd(params, cache, prompt)
    out = [np.asarray(logits[:, -1], np.float32)]
    for _ in range(steps):
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        logits, cache = fwd(params, cache, tok)
        out.append(np.asarray(logits[:, -1], np.float32))
    return out


@pytest.mark.parametrize("family", list(FAMILIES))
def test_bf16_storage_gives_the_f32_stored_logits_bitwise(family):
    cfg = configs.get_smoke(FAMILIES[family])
    f32, stored = _trees(cfg)
    prompt = jax.random.randint(KEY, (2, 7), 0, cfg.vocab_size)
    want = _greedy(cfg, f32, prompt, 8)
    got = _greedy(cfg, stored, prompt, 8)
    for step, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(g, w, err_msg=f"step {step}")
        np.testing.assert_array_equal(g.argmax(-1), w.argmax(-1))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_storage_rule_leaf_dtypes(family):
    cfg = configs.get_smoke(FAMILIES[family])
    stored = jax.eval_shape(
        lambda: transformer.init(cfg, KEY, dtype=jnp.bfloat16))
    flat = jax.tree_util.tree_flatten_with_path(stored)[0]
    seen = set()
    for path, leaf in flat:
        name = _leaf_path(path)[-1]
        seen.add(name)
        want = jnp.float32 if name in F32_LEAVES else jnp.bfloat16
        assert leaf.dtype == want, (jax.tree_util.keystr(path), leaf.dtype)
    # each config holds leaves of both groups
    assert seen & F32_LEAVES and seen - F32_LEAVES


def test_server_init_call_takes_the_benchmark_substitution(monkeypatch):
    """`bench/harness.py` replaces `transformer.init` with a function of
    this signature while it builds the `Server`; a call it cannot take
    would fail every benchmark cell."""
    real_init = transformer.init
    calls = []

    def seeded_init(cfg_, key, dtype=jnp.float32):
        calls.append(dtype)
        return real_init(cfg_, key, dtype)
    monkeypatch.setattr(transformer, "init", seeded_init)
    server = Server(configs.get_smoke("qwen3_14b"), 2, 16,
                    autotune_kernels=False)
    assert calls == [jnp.bfloat16]
    blocks = server.params["blocks"]
    for leaf in (server.params["embed"]["table"],
                 server.params["head"]["table"],
                 blocks["mixer"]["wq"], blocks["mixer"]["wo"],
                 blocks["mlp"]["w_gate"], blocks["mlp"]["w_down"]):
        assert leaf.dtype == jnp.bfloat16
    assert blocks["ln1"]["scale"].dtype == jnp.float32
    nbytes = sum(a.nbytes for a in jax.tree.leaves(server.params))
    assert sum(server.param_bytes.values()) == nbytes
    assert server.param_bytes["bfloat16"] > server.param_bytes["float32"]
