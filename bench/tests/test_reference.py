"""Each model module's plain reference against the program's own float32
forward, on the same seeded weights, at a width a CPU test holds: qk-norm
and QKV bias, RoPE, GQA, the MLP and the head must agree to float32
rounding.  The int8 control must not.  The seeded weights and the
reference's logits are pinned bit for bit, and a configuration whose
``model_type`` has no module fails as its cell is loaded."""

import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, models, weights

DATA = pathlib.Path(__file__).resolve().parent / "data"
ROOT = pathlib.Path(__file__).resolve().parents[2]


def tiny(**over):
    spec = json.loads((DATA / "tiny.config.json").read_text())
    spec.update(over)
    return spec


def program_logits(spec, seed, tokens):
    from repro.models import transformer
    cfg = models.for_spec(spec).program_config(spec)
    params = transformer.init(cfg, jax.random.PRNGKey(1), jnp.float32)
    params = weights.program_params(spec, seed, params)
    with jax.default_matmul_precision("highest"):
        out, _, _ = transformer.forward(cfg, params,
                                        {"tokens": jnp.asarray(tokens)[None]},
                                        compute_dtype=jnp.float32)
    return np.asarray(out[0])


# (spec overrides, sha256 of the seeded weights, sha256 of the reference's
# logits over the 700 tokens): pinned, so that an edit that moves what the
# benchmark draws or computes fails here, on the CPU.
CASES = {
    "qk-norm and QKV bias": (
        {},
        "fe6023ac056e347f065a67e0e6bd2cfb9c7678654f68945664ff6427c47ae6a9",
        "e98bccb86b6591fa0d61c83a80bd8c2fdd514cb4b432d8dc1053d6642bad059b"),
    "QKV bias alone": (
        {"qk_norm": False, "model_type": "qwen2"},
        "1c8bd035e8161fca1800ac26c33a8e545b64ef9b931470b03b1b89733e5200d5",
        "a44b7f7c4324d0bf58ffbbc8bf2120b5bf17c24fc2e1b16724ea8fa8ff5f41dd"),
    "qk-norm alone": (
        {"qkv_bias": False},
        "776fcc0cba0f0df0204079a8cd3e867b51918d1f3d2e94648d6b623548f21d95",
        "b5f33eb30ad38c32903a53daa136a917a88d4b78cf6980ff309976431004104c"),
}
SEED = 2**33 + 5


def case_tokens(spec):
    return np.random.default_rng(0).integers(0, spec["vocab_size"], 700)


def digest(named_arrays) -> str:
    h = hashlib.sha256()
    for name, a in named_arrays:
        a = np.asarray(a)
        h.update(f"{name}{a.shape}{a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("case", CASES)
def test_reference_matches_the_program_in_float32(case):
    spec = tiny(**CASES[case][0])
    model = models.for_spec(spec)
    tokens = case_tokens(spec)
    want = program_logits(spec, SEED, tokens)
    w = weights.make(spec, SEED)
    got = model.logits(w, spec, tokens, 0, tokens.size)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())
    tail = model.logits(w, spec, tokens, 650, 50)
    np.testing.assert_allclose(tail, got[650:], rtol=0, atol=1e-5)


@pytest.mark.parametrize("case", CASES)
def test_the_weights_and_the_reference_logits_are_pinned(case):
    over, weights_sha, logits_sha = CASES[case]
    spec = tiny(**over)
    w = weights.make(spec, SEED)
    assert digest(sorted(w.items())) == weights_sha
    tokens = case_tokens(spec)
    got = models.for_spec(spec).logits(w, spec, tokens, 0, tokens.size)
    assert digest([("logits", got)]) == logits_sha


def test_an_unknown_model_type_fails_as_its_cell_is_loaded(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    spec = json.loads((ROOT / config["file"]).read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / config["file"]).parent.mkdir(parents=True)
    (tmp_path / config["file"]).write_text(
        json.dumps({**spec, "model_type": "no_such_model"}))
    with pytest.raises(KeyError, match="no_such_model") as err:
        harness.load_cell(cell["name"], root=tmp_path)
    assert all(m in str(err.value) for m in models.known())
    assert harness.load_cell(cell["name"])["spec"] == spec


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
    reference = models.for_spec(spec)
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
