"""The plain reference against the program's own float32 forward, on the
same seeded weights, at a width a CPU test holds: qk-norm and QKV bias,
RoPE, GQA, the MLP and the head must agree to float32 rounding.  The int8
control must not."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, weights

DATA = pathlib.Path(__file__).resolve().parent / "data"


def tiny(**over):
    spec = json.loads((DATA / "tiny.config.json").read_text())
    spec.update(over)
    return spec


def program_logits(spec, seed, tokens):
    from repro.models import transformer
    cfg = harness.model_config(spec)
    params = transformer.init(cfg, jax.random.PRNGKey(1), jnp.float32)
    params = weights.program_params(spec, seed, params)
    with jax.default_matmul_precision("highest"):
        out, _, _ = transformer.forward(cfg, params,
                                        {"tokens": jnp.asarray(tokens)[None]},
                                        compute_dtype=jnp.float32)
    return np.asarray(out[0])


@pytest.mark.parametrize("over", [
    {},                                          # qk-norm and QKV bias
    {"qk_norm": False, "model_type": "qwen2"},   # QKV bias alone
    {"qkv_bias": False},                         # qk-norm alone
])
def test_reference_matches_the_program_in_float32(over):
    spec = tiny(**over)
    seed = 2**33 + 5
    tokens = np.random.default_rng(0).integers(0, spec["vocab_size"], 700)
    want = program_logits(spec, seed, tokens)
    w = weights.make(spec, seed)
    got = reference.logits(w, spec, tokens, 0, tokens.size)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())
    tail = reference.logits(w, spec, tokens, 650, 50)
    np.testing.assert_allclose(tail, got[650:], rtol=0, atol=1e-5)


def test_the_weights_are_the_seeds():
    spec = tiny()
    a = weights.make(spec, 12)
    b = weights.make(spec, 12)
    c = weights.make(spec, 13)
    for name in a:
        assert a[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(a[name], b[name])
        assert not np.array_equal(a[name], c[name])


def test_served_gap_is_zero_for_the_reference_tokens_and_not_for_others():
    spec = tiny()
    w = weights.make(spec, 3)
    prompt = np.random.default_rng(1).integers(0, spec["vocab_size"], 40)
    seq = list(prompt)
    for _ in range(12):                       # greedy through the reference
        nxt = reference.logits(w, spec, seq, len(seq) - 1, 1)[0].argmax()
        seq.append(int(nxt))
    served = np.asarray(seq[len(prompt):])
    gap = reference.served_gap(w, spec, prompt, served,
                               controls=("int8", "fp8"))
    assert gap["gap"] == 0.0 and gap["tokens"] == 12
    wrong = served.copy()
    wrong[5] = (wrong[5] + 1) % spec["vocab_size"]
    assert reference.served_gap(w, spec, prompt, wrong)["gap"] > 0.0
