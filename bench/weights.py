"""The model's weights, made on the device from the seed.

Weights are named as in the published checkpoints (``q_proj``,
``gate_proj``, ...) and stacked over layers.  Every value is drawn in
float32 and rounded to bfloat16, the precision they are served in, so the
program (which may store them wider) and the plain reference see the very
same numbers.  ``program_params`` makes them as the program's own
parameter tree, in the dtypes the program chose, in one jitted call.

Scales: projections and embeddings N(0, 0.02), as the program's own init;
norm scales 1 + N(0, 0.2) and QKV biases N(0, 0.5), so that a path that
skipped a norm's scale or a bias would move the logits well past rounding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# The program's parameter paths (``launch/serve.py``'s ``Server.params``)
# and the published names they hold.
PROGRAM_PATHS = {
    "embed/table": "embed_tokens",
    "head/table": "lm_head",
    "final_norm/scale": "norm",
    "blocks/ln1/scale": "input_layernorm",
    "blocks/ln2/scale": "post_attention_layernorm",
    "blocks/mixer/wq": "q_proj",
    "blocks/mixer/wk": "k_proj",
    "blocks/mixer/wv": "v_proj",
    "blocks/mixer/wo": "o_proj",
    "blocks/mixer/bq": "q_proj_bias",
    "blocks/mixer/bk": "k_proj_bias",
    "blocks/mixer/bv": "v_proj_bias",
    "blocks/mixer/q_norm/scale": "q_norm",
    "blocks/mixer/k_norm/scale": "k_norm",
    "blocks/mlp/w_gate": "gate_proj",
    "blocks/mlp/w_up": "up_proj",
    "blocks/mlp/w_down": "down_proj",
}


def shapes(spec: dict) -> dict:
    """Published name -> (shape, kind).  Matmul weights are (in, out)."""
    d, f, v = spec["hidden_size"], spec["intermediate_size"], \
        spec["vocab_size"]
    l = spec["num_hidden_layers"]
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    dh = spec["head_dim"]
    out = {
        "embed_tokens": ((v, d), "dense"),
        "lm_head": ((v, d), "dense"),
        "norm": ((d,), "norm"),
        "input_layernorm": ((l, d), "norm"),
        "post_attention_layernorm": ((l, d), "norm"),
        "q_proj": ((l, d, hq * dh), "dense"),
        "k_proj": ((l, d, hkv * dh), "dense"),
        "v_proj": ((l, d, hkv * dh), "dense"),
        "o_proj": ((l, hq * dh, d), "dense"),
        "gate_proj": ((l, d, f), "dense"),
        "up_proj": ((l, d, f), "dense"),
        "down_proj": ((l, f, d), "dense"),
    }
    if spec.get("qkv_bias"):
        out.update({"q_proj_bias": ((l, hq * dh), "bias"),
                    "k_proj_bias": ((l, hkv * dh), "bias"),
                    "v_proj_bias": ((l, hkv * dh), "bias")})
    if spec.get("qk_norm"):
        out.update({"q_norm": ((l, dh), "norm"), "k_norm": ((l, dh), "norm")})
    return out


SCALE = {"dense": 0.02, "bias": 0.5, "norm": 0.2}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low and high 32 bits are
    folded in one after the other)."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _draw(key, name: str, shape, kind: str, names: list) -> jax.Array:
    k = jax.random.fold_in(key, names.index(name))
    x = jax.random.normal(k, shape, jnp.float32) * SCALE[kind]
    if kind == "norm":
        x = x + 1.0
    return x.astype(jnp.bfloat16)


def make(spec: dict, seed: int) -> dict:
    """The published-name weights, bfloat16, on the default device."""
    table = shapes(spec)
    names = sorted(table)

    @jax.jit
    def gen(key):
        return {n: _draw(key, n, *table[n], names) for n in names}
    return gen(seed_key(seed))


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def program_params(spec: dict, seed: int, like):
    """This seed's weights as the program's parameter tree: ``like`` is
    that tree (arrays, or the shapes `jax.eval_shape` gives), and each
    leaf comes out in its dtype."""
    table = shapes(spec)
    names = sorted(table)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    plan = []
    for kp, leaf in leaves:
        path = _path(kp)
        name = PROGRAM_PATHS.get(path)
        if name is None or name not in table:
            raise KeyError(f"program parameter {path!r} has no published "
                           f"weight in this configuration")
        if tuple(leaf.shape) != table[name][0]:
            raise ValueError(f"program parameter {path} is {leaf.shape}, "
                             f"the configuration's {name} is "
                             f"{table[name][0]}")
        plan.append((name, leaf.dtype))

    @jax.jit
    def fill(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _draw(key, n, *table[n], names).astype(dt) for n, dt in plan])
    return fill(seed_key(seed))
