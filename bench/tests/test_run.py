"""A whole run at a width a CPU test holds, with the look for a chip
skipped: a sound run is correct, and a run whose timed path is broken
underneath is not."""

import json
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import harness
from bench.models import qwen3

DATA = pathlib.Path(__file__).resolve().parent / "data"
ROOT = pathlib.Path(__file__).resolve().parents[2]
SECONDS = 3.0


def tiny_cell(**over):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = json.loads((DATA / "tiny.config.json").read_text())
    return {"bench": {**bench, "workloads": [{"name": "tiny.chat"}],
                      "end_to_end": [{**m, "workloads": ["tiny.chat"]}
                                     for m in bench["end_to_end"]]},
            "cell": {"name": "tiny.chat", "chips": 1},
            "spec": {**spec, **over},
            "mix": json.loads((DATA / "tiny.mix.json").read_text())}


def run(mutate=None, seed=1, controls=(), cell=None):
    return harness.run_cell("tiny.chat", seed, SECONDS, False,
                            require_tpu=False, cell=cell or tiny_cell(),
                            mutate=mutate, controls=controls)


def alter_a_token(server):
    """A token altered where it is produced: every fifth decode step, the
    first active slot's."""
    inner, calls = server.decode_step, [0]

    def step(*a, **kw):
        nxt, done, bad = inner(*a, **kw)
        calls[0] += 1
        if calls[0] % 5 == 0:
            nxt = np.asarray(nxt).copy()
            s = int(np.flatnonzero(server.slot_req >= 0)[0])
            nxt[s, 0] = (nxt[s, 0] + 1) % server.cfg.vocab_size
        return nxt, done, bad
    server.decode_step = step


def keep_the_state(server):
    """A decode step that returns its state (the KV cache) unchanged."""
    inner = server.serve_step

    def step(params, cache, tokens, active=None, poison=None):
        nxt, ok, new = inner(params, cache, tokens, active, poison)
        return (nxt, ok, cache) if tokens.shape[1] == 1 else (nxt, ok, new)
    server.serve_step = step


def test_a_sound_run_is_correct_and_its_fp8_control_is_not():
    """The control: the reference in fp8, one step below the stated bf16,
    put in the program's place at the same prompts and served tokens."""
    res = run(controls=("fp8",))
    assert res["correct"] is True
    assert res["attempted"] == round(4.0 * SECONDS) and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "ttft_p50_ms",
                                   "itl_p50_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] < res["checks"][
        "fp8_control_gap"]["value"]
    chk = res["checks"]["logit_gap"]
    assert chk["value"] <= chk["limit"] and chk["correct"] is True
    ctl = res["checks"]["fp8_control_gap"]
    assert ctl["value"] > ctl["limit"] and ctl["correct"] is False


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("fault", [alter_a_token, keep_the_state])
def test_a_broken_timed_path_is_not_correct(fault, seed):
    res = run(fault, seed)
    assert res["correct"] is False
    chk = res["checks"]["logit_gap"]
    assert chk["value"] > chk["limit"] and chk["correct"] is False


def test_a_model_module_added_as_new_code_is_the_one_a_run_uses(
        monkeypatch):
    """A model module registered under its ``model_type`` alone (here the
    Qwen3 module's functions, each recording its calls) gives the run its
    program config, its weight table and parameter paths, and its check."""
    calls = []

    def recorded(fn):
        def call(*a, **kw):
            calls.append(fn.__name__)
            return fn(*a, **kw)
        return call

    class Paths(dict):
        def get(self, key, default=None):
            calls.append("PROGRAM_PATHS")
            return super().get(key, default)

    stub = types.ModuleType("bench.models.stub_decoder")
    stub.program_config = recorded(qwen3.program_config)
    stub.weight_shapes = recorded(qwen3.weight_shapes)
    stub.served_gap = recorded(qwen3.served_gap)
    stub.PROGRAM_PATHS = Paths(qwen3.PROGRAM_PATHS)
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    res = run(cell=tiny_cell(model_type="stub_decoder"))
    assert res["correct"] is True
    # build: the config, then the weights as the program's tree; check:
    # the weights by name, then the reference over each sampled request.
    assert calls.index("program_config") < calls.index("PROGRAM_PATHS")
    assert calls.count("weight_shapes") == 2
    assert "served_gap" in calls and calls[-1] == "served_gap"


def test_no_chip_no_result(tmp_path):
    with pytest.raises(harness.NoChip):
        harness.run_cell("qwen3_14b.chat", 1, 1.0, False)


def test_no_program_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    out = subprocess.run([sys.executable, "bench/run.py", "--workload",
                          "qwen3_14b.chat", "--seed", "1", "--seconds", "1",
                          "--trace", "0"], cwd=tmp_path, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
