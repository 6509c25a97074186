"""The per-step work counts against the hand reckoning of the Qwen3-14B
one-chip cut (2 of 40 layers, every published width)."""

import json
import pathlib

import pytest

from bench import counts

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def spec(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_qwen3_cut_params_and_bytes():
    s = spec("qwen3_14b_1chip")
    assert counts.layer_matmul_params(s) * 2 == pytest.approx(660.6e6, abs=0.05e6)
    assert counts.head_params(s) == pytest.approx(777.9e6, abs=0.05e6)
    # weights the decode step reads, bf16: layers + final norm + head
    assert counts.decode_weight_bytes(s) == pytest.approx(
        2 * (660.6e6 + 777.9e6), rel=1e-3)
    assert counts.kv_bytes_per_token(s, "bf16") == 8 * 1024


def test_qwen3_prefill_flops_per_token():
    s = spec("qwen3_14b_1chip")
    # the layers' matmuls: 2 FLOPs per parameter per token
    assert 2 * counts.layer_matmul_params(s) * 2 == pytest.approx(1.32e9, rel=2e-3)
    # a prompt adds causal attention and one head row on top
    p = 512
    extra = counts.prefill_flops(s, p) - 2 * counts.layer_matmul_params(s) * 2 * p
    attn = 2 * 4 * 40 * 128 * (p * (p + 1) // 2)
    assert extra == attn + 2 * 151936 * 5120


def test_qwen2_5_cut():
    s = spec("qwen2_5_32b_1chip")
    assert counts.layer_matmul_params(s) == pytest.approx(487.6e6, abs=0.05e6)
    assert counts.layer_params(s) - counts.layer_matmul_params(s) == (
        2 * 5120 + 5120 + 2 * 1024)
    assert counts.kv_bytes_per_token(s, "bf16") == 4 * 1024


def test_decode_bytes_count_the_kv_prefix():
    s = spec("qwen3_14b_1chip")
    base = counts.decode_step_bytes(s, "bf16", 0, 0)
    assert base == counts.decode_weight_bytes(s)
    two = counts.decode_step_bytes(s, "bf16", 1000, 2)
    assert two - base == 1000 * 8192 + 2 * (8192 + 5120 * 2)


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_s(1000, 10, peaks) == 10.0
    assert counts.roofline_s(10, 1000, peaks) == 100.0
