"""KernelSpec registrations for the flash-attention families.

Two specs live here: ``attention`` (the block-skipping prefill kernel,
knobs = (block_q, block_k)) and ``decode`` (the fused single-query
KV-cache kernel, knob = block_k).  Candidate enumeration moved out of
`core/dse.py`'s `rank_attention_blocks`/`rank_decode_blocks`; the cost
wrappers delegate to `cost_model.attention_time_model` /
`decode_time_model`.  Both families dispatch inside jit traces at serving
time, so their ``default_measure_k`` is 0 — measured winners come from
offline callers (benchmarks) through the shared cache.
"""

from __future__ import annotations

from typing import Sequence

import jax

from repro.core import cost_model, dse, hardware
from repro.kernels import registry
from repro.kernels.attention import decode as attn_decode
from repro.kernels.attention import decode_int8 as attn_decode_int8
from repro.kernels.attention import kernel as attn_kernel
from repro.kernels.attention import ops as attn_ops
from repro.runtime import quantize


# ---------------------------------------------------------------------------
# Prefill flash attention
# ---------------------------------------------------------------------------

def rank_attention_blocks(
    bh: int, sq: int, sk: int, dh: int,
    vmem_bytes: int | None = None,
    dtype_bytes: int = 2,
    causal: bool = True,
    window: int | None = None,
    block_cands: Sequence[int] = (128, 256, 512, 1024),
    top: int = 8,
) -> list[dse.Candidate]:
    """Sweep (block_q, block_k) pairs for the flash-attention kernel; score
    with `cost_model.attention_time_model` under the VMEM budget.

    The kernel clamps blocks to the sequence (``min(block, s)``) and pads
    ragged remainders, so candidates are enumerated in *effective* block
    space and deduped — a 64-token prefill collapses every block_q
    candidate onto 64.  The mask enters the score: with block skipping the
    model credits the causal triangle / window band, so the ranking trades
    deeper q-blocks (less K/V re-streaming) against coarser masked-area
    coverage instead of assuming every block runs.  Ranking is
    deterministic: model time with (block_q, block_k) as the tie-break,
    descending block_q preferred on ties.  Each ``Candidate.detail``
    carries the effective blocks plus the model row.  Never returns empty:
    if the budget rejects everything, the smallest legal pair is scored and
    returned anyway (the kernel itself is the final arbiter on real VMEM).
    """
    chip = hardware.TPU_V5E
    budget = vmem_bytes if vmem_bytes is not None else chip.usable_vmem()

    # The kernel pads ragged remainders (and masks the tail), so candidates
    # need not divide the sequence — enumerate effective (clamped) blocks
    # and dedupe; a 64-token prefill still collapses onto a single pair.
    pairs = []
    seen = set()
    for bq in block_cands:
        for bk in block_cands:
            ebq, ebk = min(bq, sq), min(bk, sk)
            if (ebq, ebk) in seen:
                continue
            seen.add((ebq, ebk))
            pairs.append({"block_q": ebq, "block_k": ebk})

    def evaluate(knobs: dict) -> tuple[float, dict]:
        res = cost_model.attention_time_model(
            bh, sq, sk, dh, knobs["block_q"], knobs["block_k"],
            causal=causal, window=window, dtype_bytes=dtype_bytes)
        if res["vmem_bytes"] > budget:
            return float("inf"), {}
        return res["time_s"], {**knobs, **res}

    # Score ALL pairs before truncating: explore()'s internal top-cut is
    # insertion-ordered on ties, which would drop the deeper-block_q
    # candidates the tie-break below exists to prefer.
    ranked = dse.explore(pairs, evaluate, top=len(pairs))
    ranked = [c for c in ranked if c.detail and "block_q" in c.detail]
    ranked.sort(key=lambda c: (c.score, -c.detail["block_q"],
                               c.detail["block_k"]))
    if not ranked:
        knobs = min(pairs, key=lambda p: (p["block_q"], p["block_k"]))
        res = cost_model.attention_time_model(
            bh, sq, sk, dh, knobs["block_q"], knobs["block_k"],
            causal=causal, window=window, dtype_bytes=dtype_bytes)
        ranked = [dse.Candidate(knobs, res["time_s"], {**knobs, **res})]
    return ranked[:top]


def _attn_key_fn(problem: dict, dtype: str, backend: str) -> str:
    window = problem["window"]
    return (f"{problem['bh']}x{problem['sq']}x{problem['sk']}"
            f"x{problem['dh']}:c{int(problem['causal'])}"
            f":w{'none' if window is None else window}:{dtype}:{backend}")


def _attn_enumerate(problem: dict, dtype_bytes: int,
                    vmem_bytes: int | None, top: int) -> list[dse.Candidate]:
    # Over-request so the ENGINE's (score, tie_break) sort performs the
    # authoritative top-cut (the ranker's internal order serves only the
    # standalone deprecated rank_* API).
    ranked = rank_attention_blocks(
        problem["bh"], problem["sq"], problem["sk"], problem["dh"],
        vmem_bytes=vmem_bytes, dtype_bytes=dtype_bytes,
        causal=problem["causal"], window=problem["window"],
        top=max(top, 8))
    return [dse.Candidate({"block_q": c.detail["block_q"],
                           "block_k": c.detail["block_k"]}, c.score, {})
            for c in ranked]


def _attn_cost_fn(problem: dict, knobs: dict, dtype_bytes: int = 2) -> dict:
    return cost_model.attention_time_model(
        problem["bh"], problem["sq"], problem["sk"], problem["dh"],
        knobs["block_q"], knobs["block_k"], causal=problem["causal"],
        window=problem["window"], dtype_bytes=dtype_bytes)


def _attn_make_inputs(problem: dict, dtype) -> tuple:
    bh, sq, sk, dh = (problem["bh"], problem["sq"], problem["sk"],
                      problem["dh"])
    q = jax.random.normal(jax.random.PRNGKey(0), (bh, sq, dh), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, sk, dh), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, sk, dh), dtype)
    return q, k, v


def _attn_build_launcher(problem: dict, knobs: dict, interpret: bool):
    scale = 1.0 / (problem["dh"] ** 0.5)
    return lambda q, k, v: attn_kernel.flash_attention(
        q, k, v, scale=scale, causal=problem["causal"],
        window=problem["window"], block_q=knobs["block_q"],
        block_k=knobs["block_k"], interpret=interpret)


def _attn_problem_fn(q, k, v, causal=True, window=None, q_offset=None,
                     kv_len=None) -> tuple[dict, object]:
    b, sq, hq, dh = q.shape
    _, sk, _, _ = k.shape
    return {"bh": b * hq, "sq": sq, "sk": sk, "dh": dh,
            "causal": causal, "window": window}, q.dtype


def _attn_run_fn(plan: registry.Plan, q, k, v, *, interpret=False,
                 causal=True, window=None, q_offset=None, kv_len=None):
    return attn_ops.mha_attention(q, k, v, causal=causal, window=window,
                                  block_q=plan.knobs["block_q"],
                                  block_k=plan.knobs["block_k"],
                                  interpret=interpret, use_kernel=True,
                                  q_offset=q_offset, kv_len=kv_len)


def _attn_reference_fn(q, k, v, causal=True, window=None, q_offset=None,
                       kv_len=None):
    return attn_ops.mha_attention(q, k, v, causal=causal, window=window,
                                  use_kernel=False, q_offset=q_offset,
                                  kv_len=kv_len)


registry.register(registry.KernelSpec(
    name="attention",
    key_fn=_attn_key_fn,
    enumerate_candidates=_attn_enumerate,
    cost_fn=_attn_cost_fn,
    make_inputs=_attn_make_inputs,
    build_launcher=_attn_build_launcher,
    reference_fn=_attn_reference_fn,
    problem_fn=_attn_problem_fn,
    run_fn=_attn_run_fn,
    measure_elems=lambda p: p["bh"] * (p["sq"] + 2 * p["sk"]) * p["dh"],
    tie_break=lambda knobs: (-knobs["block_q"], knobs["block_k"]),
    default_measure_k=0,     # dispatched inside the serving jit trace
    bench_key="attention_tuned_vs_fixed",
))


# ---------------------------------------------------------------------------
# Fused single-query decode attention
# ---------------------------------------------------------------------------

def rank_decode_blocks(
    bkv: int, g: int, kv_len: int, dh: int,
    vmem_bytes: int | None = None,
    dtype_bytes: int = 2,
    block_cands: Sequence[int] = (128, 256, 512, 1024, 2048),
    top: int = 8,
    lengths: Sequence[int] | None = None,
) -> list[dse.Candidate]:
    """Sweep block_k for the fused decode-attention kernel
    (kernels/attention/decode.py); score with
    `cost_model.decode_time_model` under the VMEM budget.

    ``bkv = batch*kv_heads`` folded rows, ``g`` the GQA query group riding
    each row, ``kv_len`` the KV-cache depth the server allocated.  The knob
    trades tail over-fetch (coarse block_k rounds the cache up) against
    grid-step count; ranking is deterministic — model time, then *larger*
    block_k on ties (fewer grid steps for the same traffic).  Never empty:
    the smallest candidate is scored unconditionally if the budget rejects
    everything (the kernel is the final arbiter on real VMEM).

    ``lengths`` (optional) is a ragged batch's per-sequence valid-prefix
    distribution: candidates are scored on each row's block-rounded
    *active prefix* instead of the full ``kv_len``, so a batch mixing
    shallow and deep slots prefers a finer block_k that lets the shallow
    rows skip — the fetched-vs-active load-balancing argument applied to
    the serving plan.
    """
    chip = hardware.TPU_V5E
    budget = vmem_bytes if vmem_bytes is not None else chip.usable_vmem()

    cands = sorted({min(bk, max(kv_len, 1)) for bk in block_cands})

    def evaluate(knobs: dict) -> tuple[float, dict]:
        res = cost_model.decode_time_model(bkv, g, kv_len, dh,
                                           knobs["block_k"],
                                           dtype_bytes=dtype_bytes,
                                           lengths=lengths)
        if res["vmem_bytes"] > budget:
            return float("inf"), {}
        return res["time_s"], {**knobs, **res}

    ranked = dse.explore([{"block_k": bk} for bk in cands], evaluate,
                         top=len(cands))
    ranked = [c for c in ranked if c.detail and "block_k" in c.detail]
    ranked.sort(key=lambda c: (c.score, -c.detail["block_k"]))
    if not ranked:
        bk = cands[0]
        res = cost_model.decode_time_model(bkv, g, kv_len, dh, bk,
                                           dtype_bytes=dtype_bytes,
                                           lengths=lengths)
        ranked = [dse.Candidate({"block_k": bk}, res["time_s"],
                                {"block_k": bk, **res})]
    return ranked[:top]


def _decode_key_fn(problem: dict, dtype: str, backend: str) -> str:
    # The optional per-slot length distribution is part of the key: a plan
    # tuned for a ragged workload must not shadow the batch-max one.
    lengths = problem.get("lengths")
    ltag = ("" if not lengths
            else ":l" + "-".join(str(int(l)) for l in lengths))
    return (f"{problem['bkv']}x{problem['g']}x{problem['cache_len']}"
            f"x{problem['dh']}{ltag}:{dtype}:{backend}")


def _decode_lengths(problem: dict) -> list[int] | None:
    lengths = problem.get("lengths")
    return list(lengths) if lengths else None


def _decode_enumerate(problem: dict, dtype_bytes: int,
                      vmem_bytes: int | None, top: int) -> list[dse.Candidate]:
    # Over-request: the engine's tie_break performs the authoritative cut.
    ranked = rank_decode_blocks(
        problem["bkv"], problem["g"], problem["cache_len"], problem["dh"],
        vmem_bytes=vmem_bytes, dtype_bytes=dtype_bytes, top=max(top, 8),
        lengths=_decode_lengths(problem))
    return [dse.Candidate({"block_k": c.detail["block_k"]}, c.score, {})
            for c in ranked]


def _decode_cost_fn(problem: dict, knobs: dict, dtype_bytes: int = 2) -> dict:
    return cost_model.decode_time_model(
        problem["bkv"], problem["g"], problem["cache_len"], problem["dh"],
        knobs["block_k"], dtype_bytes=dtype_bytes,
        lengths=_decode_lengths(problem))


def _decode_make_inputs(problem: dict, dtype) -> tuple:
    bkv, g, cache_len, dh = (problem["bkv"], problem["g"],
                             problem["cache_len"], problem["dh"])
    q = jax.random.normal(jax.random.PRNGKey(0), (bkv, g, dh), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (bkv, cache_len, dh), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (bkv, cache_len, dh), dtype)
    return q, k, v


def _decode_build_launcher(problem: dict, knobs: dict, interpret: bool):
    import numpy as np

    scale = 1.0 / (problem["dh"] ** 0.5)
    # Measured at the depths the plan is priced at: the per-row ragged
    # lengths when the problem carries a distribution (each sequence's
    # length repeated across its folded KV heads), else the full cache
    # depth — the worst case the server allocated for.
    lengths = _decode_lengths(problem)
    if lengths:
        rep = problem["bkv"] // len(lengths)
        length = np.repeat(np.asarray(lengths, np.int32), rep)
    else:
        length = problem["cache_len"]
    return lambda q, k, v: attn_decode.decode_attention(
        q, k, v, scale=scale, length=length,
        block_k=knobs["block_k"], interpret=interpret)


def _decode_problem_fn(q, k, v, length=None) -> tuple[dict, object]:
    b, hq, dh = q.shape
    _, kl, hkv, _ = k.shape
    # The kernel streams the cache (and upcasts q to it), so the plan is
    # keyed and priced on the *cache* dtype — an f32 cache costs twice the
    # KV traffic of a bf16 one regardless of the activation dtype.
    return {"bkv": b * hkv, "g": hq // hkv, "cache_len": kl,
            "dh": dh}, k.dtype


def _decode_run_fn(plan: registry.Plan, q, k, v, *, interpret=False,
                   length=None):
    return attn_decode.gqa_decode_attention(q, k, v, length=length,
                                            block_k=plan.knobs["block_k"],
                                            interpret=interpret)


registry.register(registry.KernelSpec(
    name="decode",
    key_fn=_decode_key_fn,
    enumerate_candidates=_decode_enumerate,
    cost_fn=_decode_cost_fn,
    make_inputs=_decode_make_inputs,
    build_launcher=_decode_build_launcher,
    reference_fn=lambda q, k, v, length=None: attn_decode.decode_ref(
        q, k, v, length=length),
    problem_fn=_decode_problem_fn,
    run_fn=_decode_run_fn,
    measure_elems=lambda p: p["bkv"] * (p["g"] + 2 * p["cache_len"])
    * p["dh"],
    tie_break=lambda knobs: (-knobs["block_k"],),
    default_measure_k=0,     # dispatched inside the serving jit trace
    bench_key="attention_decode",
))


# ---------------------------------------------------------------------------
# Int8 quantized-streaming decode attention (kernel family #5)
# ---------------------------------------------------------------------------
# The ~50-line KernelSpec recipe: the quantized kernel shares the decode
# family's problem shape and block_k knob, but streams int8 K/V + f32
# per-row scales and is priced by `quantized_decode_time_model` — whose
# honest scale-stream + dequant-FLOP accounting lets the DSE lose to the
# bf16 stream where it should (small dh, compute-bound corners).

def _decode_int8_key_fn(problem: dict, dtype: str, backend: str) -> str:
    # `q8` tags the quantized cache layout; `dtype` remains the activation
    # dtype the q rows and output carry.
    lengths = problem.get("lengths")
    ltag = ("" if not lengths
            else ":l" + "-".join(str(int(l)) for l in lengths))
    return (f"{problem['bkv']}x{problem['g']}x{problem['cache_len']}"
            f"x{problem['dh']}{ltag}:q8:{dtype}:{backend}")


def _decode_int8_enumerate(problem: dict, dtype_bytes: int,
                           vmem_bytes: int | None,
                           top: int) -> list[dse.Candidate]:
    chip = hardware.TPU_V5E
    budget = vmem_bytes if vmem_bytes is not None else chip.usable_vmem()
    kv_len = problem["cache_len"]
    cands = sorted({min(bk, max(kv_len, 1))
                    for bk in (128, 256, 512, 1024, 2048)})

    def evaluate(knobs: dict) -> tuple[float, dict]:
        res = _decode_int8_cost_fn(problem, knobs)
        if res["vmem_bytes"] > budget:
            return float("inf"), {}
        return res["time_s"], {**knobs, **res}

    ranked = dse.explore([{"block_k": bk} for bk in cands], evaluate,
                         top=len(cands))
    ranked = [c for c in ranked if c.detail and "block_k" in c.detail]
    ranked.sort(key=lambda c: (c.score, -c.detail["block_k"]))
    if not ranked:
        bk = cands[0]
        res = _decode_int8_cost_fn(problem, {"block_k": bk})
        ranked = [dse.Candidate({"block_k": bk}, res["time_s"],
                                {"block_k": bk, **res})]
    return [dse.Candidate({"block_k": c.detail["block_k"]}, c.score, {})
            for c in ranked[:top]]


def _decode_int8_cost_fn(problem: dict, knobs: dict,
                         dtype_bytes: int = 1) -> dict:
    # dtype_bytes is fixed by the layout (int8 values + f32 scales); the
    # engine's argument is accepted and ignored.
    return cost_model.quantized_decode_time_model(
        problem["bkv"], problem["g"], problem["cache_len"], problem["dh"],
        knobs["block_k"], lengths=_decode_lengths(problem))


def _decode_int8_make_inputs(problem: dict, dtype) -> tuple:
    bkv, g, cache_len, dh = (problem["bkv"], problem["g"],
                             problem["cache_len"], problem["dh"])
    q = jax.random.normal(jax.random.PRNGKey(0), (bkv, g, dh), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (bkv, cache_len, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (bkv, cache_len, dh))
    kq, ks = quantize.quantize_rows(k)
    vq, vs = quantize.quantize_rows(v)
    return q, kq, ks, vq, vs


def _decode_int8_build_launcher(problem: dict, knobs: dict, interpret: bool):
    import numpy as np

    scale = 1.0 / (problem["dh"] ** 0.5)
    lengths = _decode_lengths(problem)
    if lengths:
        rep = problem["bkv"] // len(lengths)
        length = np.repeat(np.asarray(lengths, np.int32), rep)
    else:
        length = problem["cache_len"]
    return lambda q, kq, ks, vq, vs: attn_decode_int8.quantized_decode_attention(
        q, kq, ks, vq, vs, scale=scale, length=length,
        block_k=knobs["block_k"], interpret=interpret)


def _decode_int8_problem_fn(q, kq, ks, vq, vs,
                            length=None) -> tuple[dict, object]:
    b, hq, dh = q.shape
    _, kl, hkv, _ = kq.shape
    # The cache layout is fixed (int8 + f32 scales, tagged `q8` in the
    # key), so unlike the float decode family the plan keys on the
    # *activation* dtype the q rows carry.
    return {"bkv": b * hkv, "g": hq // hkv, "cache_len": kl,
            "dh": dh}, q.dtype


def _decode_int8_run_fn(plan: registry.Plan, q, kq, ks, vq, vs, *,
                        interpret=False, length=None):
    return attn_decode_int8.quantized_gqa_decode_attention(
        q, kq, ks, vq, vs, length=length,
        block_k=plan.knobs["block_k"], interpret=interpret)


registry.register(registry.KernelSpec(
    name="decode_int8",
    key_fn=_decode_int8_key_fn,
    enumerate_candidates=_decode_int8_enumerate,
    cost_fn=_decode_int8_cost_fn,
    make_inputs=_decode_int8_make_inputs,
    build_launcher=_decode_int8_build_launcher,
    reference_fn=lambda q, kq, ks, vq, vs, length=None:
        attn_decode_int8.quantized_decode_ref(q, kq, ks, vq, vs,
                                              length=length),
    problem_fn=_decode_int8_problem_fn,
    run_fn=_decode_int8_run_fn,
    measure_elems=lambda p: p["bkv"] * (p["g"] + 2 * p["cache_len"])
    * p["dh"],
    tie_break=lambda knobs: (-knobs["block_k"],),
    default_measure_k=0,     # dispatched inside the serving jit trace
    bench_key="decode_int8",
))
