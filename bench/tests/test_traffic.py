"""The generator gives every seed the same work in another order."""

import json
import pathlib

import numpy as np

from bench import traffic

MIXES = pathlib.Path(__file__).resolve().parents[1] / "mixes"


def chat():
    return json.loads((MIXES / "qwen3_14b.chat.json").read_text())


def test_same_work_for_every_seed():
    mix = chat()
    a = traffic.requests_for(mix, 3, 30.0, 1000)
    b = traffic.requests_for(mix, 2**31 + 99, 30.0, 1000)
    assert len(a) == len(b) == round(mix["arrival"]["rate_rps"] * 30)
    for field in ("prompt_len", "output_len"):
        assert sorted(getattr(r, field) for r in a) == \
            sorted(getattr(r, field) for r in b)
        assert [getattr(r, field) for r in a] != \
            [getattr(r, field) for r in b]
    ga = sorted(np.diff([r.due_s for r in a]))
    gb = sorted(np.diff([r.due_s for r in b]))
    assert len(ga) == len(gb)


def test_same_seed_same_requests():
    mix = chat()
    a = traffic.requests_for(mix, 7, 10.0, 1000)
    b = traffic.requests_for(mix, 7, 10.0, 1000)
    assert [(r.due_s, r.prompt_len, r.output_len) for r in a] == \
        [(r.due_s, r.prompt_len, r.output_len) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_arrivals_fall_inside_the_window_and_prompts_on_buckets():
    mix = chat()
    reqs = traffic.requests_for(mix, 11, 20.0, 1000)
    assert reqs[0].due_s == 0.0
    assert all(0.0 <= r.due_s < 20.0 for r in reqs)
    assert all(r.prompt_len in mix["prompt_buckets"] for r in reqs)
    assert all(r.prompt.size == r.prompt_len for r in reqs)
    lo, hi = mix["output"]["lo"], mix["output"]["hi"]
    assert all(lo <= r.output_len <= hi for r in reqs)


def test_quantile_lengths_follow_the_lognormal():
    dist = {"kind": "lognormal", "median": 512, "sigma": 0.8, "lo": 1,
            "hi": 10**9}
    vals = traffic.quantile_lengths(1001, dist)
    assert vals[500] == 512
    assert vals == sorted(vals)


def test_every_block_holds_one_of_each_stratum():
    vals = list(range(60))
    out = traffic.dealt(vals, np.random.default_rng(1))
    assert sorted(out) == vals
    blocks = [out[i:i + traffic.BLOCK] for i in range(0, 60, traffic.BLOCK)]
    for b in blocks:                     # 10 blocks, strata of 10 values
        assert sorted(v // 10 for v in b) == list(range(traffic.BLOCK))
    again = traffic.dealt(vals, np.random.default_rng(2))
    assert again != out
    assert [sorted(again[i:i + 6]) for i in range(0, 60, 6)] == \
        [sorted(b) for b in blocks]
