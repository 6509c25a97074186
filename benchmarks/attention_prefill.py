"""Attention prefill benchmark: tuned vs fixed-tile flash attention.

The third kernel family through the tuner-vs-fixed lens (matmul:
table1_matmul, SpMV: table2_spmv).  'fixed' is what `mha_attention` callers
ran before the engine: the hand-picked (512, 512) default block pair.
'tuned' goes through the full DSE -> (measure) -> cache path
(`autotune.tune("attention", ...)`).  Shapes are the serving prefill shapes — the
(batch*heads, prompt, prompt, head_dim) folds `launch.serve` pre-tunes at
startup — derived from real arch configs so the benchmark tracks what the
server actually runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.core import cost_model
from repro.kernels import autotune, registry
from repro.kernels.attention import kernel as attn_kernel

# (arch, serving batch, prompt length) -> the prefill fold the server tunes.
PREFILL_POINTS = [
    ("qwen3_14b", 8, 2048),
    ("qwen3_14b", 8, 8192),
    ("phi3_mini_3_8b", 16, 4096),
    ("h2o_danube_1_8b", 32, 2048),
]

FIXED_BLOCK = 512           # mha_attention's pre-engine default


def _interleaved_best_us(thunks: dict, reps: int, trials: int) -> dict:
    """Best-of-``trials`` wall time per config, measured interleaved so
    machine drift hits all configs alike (the table1 timing discipline).
    ``thunks``: {key: zero-arg callable returning a jax array}."""
    slots = {key: float("inf") for key in thunks}
    for _ in range(trials):
        for key, fn in thunks.items():
            slots[key] = min(slots[key], autotune.measure(fn, reps=reps))
    return slots


def prefill_shapes():
    out = []
    for arch, batch, prompt in PREFILL_POINTS:
        cfg = configs.get(arch)
        out.append({
            "arch": cfg.name, "batch": batch, "prompt": prompt,
            "bh": batch * cfg.num_heads, "sq": prompt, "sk": prompt,
            "dh": cfg.head_dim, "causal": cfg.causal,
            "window": cfg.sliding_window,
        })
    return out


def tuned_vs_fixed():
    """Tuner vs the fixed (512, 512) blocks on the serving prefill shapes.

    Both sides are scored by the same machine model
    (`cost_model.attention_time_model`); the tuner's candidate set contains
    the fixed pair whenever it is feasible, so ``speedup_model >= 1`` unless
    a wall-clock measurement overrode the analytic winner (then
    ``measured_us`` is the evidence, as in table1).
    """
    recs = []
    for s in prefill_shapes():
        fq = min(FIXED_BLOCK, s["sq"])
        fk = min(FIXED_BLOCK, s["sk"])
        problem = {"bh": s["bh"], "sq": s["sq"], "sk": s["sk"],
                   "dh": s["dh"], "causal": s["causal"],
                   "window": s["window"]}
        spec = registry.get("attention")
        fixed = cost_model.attention_time_model(
            s["bh"], s["sq"], s["sk"], s["dh"], fq, fk, causal=s["causal"],
            window=s["window"])
        plan = autotune.tune("attention", problem, jnp.bfloat16)
        tuned = spec.cost_fn(problem, plan.knobs)
        recs.append({
            "arch": s["arch"], "batch": s["batch"], "prompt": s["prompt"],
            "shape": [s["bh"], s["sq"], s["sk"], s["dh"]],
            "fixed_block": [fq, fk],
            "tuned_block": [plan.knobs["block_q"], plan.knobs["block_k"]],
            "tuned_source": plan.source,
            "tuned_measured_us": plan.measured_us,
            "gflops_fixed_model": fixed["gflops"],
            "gflops_tuned_model": tuned["gflops"],
            "speedup_model": fixed["time_s"] / tuned["time_s"],
        })
    return recs


def causal_skip_measured(bh: int = 2, seq: int = 1024, dh: int = 32,
                         block_q: int = 128, block_k: int = 128,
                         reps: int = 3, trials: int = 3):
    """Block-skipping vs dense execution of the causal kernel at the SAME
    (block_q, block_k) — the tentpole's perf claim, recorded two ways:

    * ``kstep_speedup``: dense grid block pairs / active block pairs
      (`cost_model.attention_active_block_pairs`) — the exact count of
      K-steps the kernel streams and multiplies, deterministic on any
      backend (>= 1.5x for >= 3 q-blocks, ~2x asymptotically at sq=sk);
    * ``wall_speedup``: interleaved best-of-``trials`` wall-clock of the
      two kernels (interpret mode off-TPU, so grid overhead dilutes it —
      the K-step count is the load-bearing number there).
    """
    interpret = jax.default_backend() != "tpu"
    scale = 1.0 / (dh ** 0.5)
    q = jax.random.normal(jax.random.PRNGKey(0), (bh, seq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, seq, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, seq, dh), jnp.float32)

    slots = _interleaved_best_us({
        skip: (lambda skip=skip: attn_kernel.flash_attention(
            q, k, v, scale=scale, causal=True, block_q=block_q,
            block_k=block_k, interpret=interpret, block_skipping=skip))
        for skip in (True, False)}, reps, trials)

    active, total = cost_model.attention_active_block_pairs(
        seq, seq, block_q, block_k, causal=True)
    return {
        "shape": [bh, seq, seq, dh],
        "block": [block_q, block_k],
        "k_steps_dense": total,
        "k_steps_skip": active,
        "kstep_speedup": total / active,
        "skip_us": slots[True],
        "dense_us": slots[False],
        "wall_speedup": slots[False] / slots[True],
        "interpret": interpret,
    }


def decode_step_measured(b: int = 2, hq: int = 8, hkv: int = 2,
                         dh: int = 64, cache_len: int = 1024,
                         length: int | None = None,
                         reps: int = 3, trials: int = 3):
    """One fused decode-attention step: tuned block_k vs the fixed (512)
    default, wall-clocked where feasible — the decode analogue of the
    tuned-vs-fixed prefill rows.  ``length`` defaults to a ragged 3/4 of
    the cache so the tail over-fetch the tuner prices actually occurs."""
    from repro.kernels.attention import decode as attn_decode

    interpret = jax.default_backend() != "tpu"
    if length is None:
        length = cache_len * 3 // 4 + 1          # ragged on purpose
    g = hq // hkv
    problem = {"bkv": b * hkv, "g": g, "cache_len": cache_len, "dh": dh}
    plan = autotune.tune("decode", problem, jnp.float32)
    tuned_bk = plan.knobs["block_k"]
    fixed_bk = min(FIXED_BLOCK, cache_len)
    scale = 1.0 / (dh ** 0.5)
    q = jax.random.normal(jax.random.PRNGKey(0), (b * hkv, g, dh),
                          jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b * hkv, cache_len, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b * hkv, cache_len, dh),
                          jnp.float32)

    slots = _interleaved_best_us({
        bk: (lambda bk=bk: attn_decode.decode_attention(
            q, k, v, scale=scale, length=length, block_k=bk,
            interpret=interpret))
        for bk in {tuned_bk, fixed_bk}}, reps, trials)

    model = registry.get("decode").cost_fn(problem, plan.knobs)
    return {
        "shape": [b * hkv, g, cache_len, dh],
        "length": length,
        "tuned_block_k": tuned_bk,
        "tuned_source": plan.source,
        "tuned_us": slots[tuned_bk],
        "fixed_block_k": fixed_bk,
        "fixed_us": slots[fixed_bk],
        "speedup_vs_fixed": slots[fixed_bk] / slots[tuned_bk],
        "model_time_us": model["time_s"] * 1e6,
        "interpret": interpret,
    }


# Declared accuracy budget for the int8 KV stream: max |attention-output
# error| of the quantized kernel vs the float oracle on the same inputs.
# tools/check_bench.py re-asserts the measured error against this budget
# (and caps the budget itself, so a report cannot fabricate a loose one).
INT8_ERR_BUDGET = 0.05


def decode_int8_measured(b: int = 2, hq: int = 8, hkv: int = 2,
                         dh: int = 64, cache_len: int = 1024,
                         length: int | None = None,
                         reps: int = 3, trials: int = 3):
    """Int8 quantized KV stream vs the bf16 stream at the same decode
    shape — the bandwidth-vs-accuracy trade the quantized family #5 is
    for, recorded two ways:

    * ``bytes_ratio``: bf16 KV bytes per token / int8+scale bytes per
      token (``quantize.bytes_per_token``) — the exact per-token stream
      the kernel fetches, deterministic on any backend (2*dh/(dh+4),
      >= 1.6x for dh >= 16, ~2x asymptotically);
    * ``tuned_us`` vs ``bf16_us``: interleaved best-of-``trials``
      wall-clock of the int8 kernel at its tuned block against the float
      decode kernel streaming a bf16 cache (interpret mode off-TPU, so
      dequant overhead dominates — the byte count is the load-bearing
      number there).

    ``max_abs_err`` is the quantized kernel's output error against the
    float-cache oracle on the same pre-quantization values; it must land
    under the declared ``err_budget`` (gated in tools/check_bench.py).
    """
    from repro.kernels.attention import decode as attn_decode
    from repro.kernels.attention import decode_int8 as attn_decode_int8
    from repro.runtime import quantize

    interpret = jax.default_backend() != "tpu"
    if length is None:
        length = cache_len * 3 // 4 + 1          # ragged on purpose
    g = hq // hkv
    problem = {"bkv": b * hkv, "g": g, "cache_len": cache_len, "dh": dh}
    plan = autotune.tune("decode_int8", problem, jnp.bfloat16)
    tuned_bk = plan.knobs["block_k"]
    scale = 1.0 / (dh ** 0.5)
    q = jax.random.normal(jax.random.PRNGKey(0), (b * hkv, g, dh),
                          jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b * hkv, cache_len, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b * hkv, cache_len, dh),
                          jnp.float32)
    kq, ks = quantize.quantize_rows(k)
    vq, vs = quantize.quantize_rows(v)
    kb, vb = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)

    slots = _interleaved_best_us({
        "int8": lambda: attn_decode_int8.quantized_decode_attention(
            q, kq, ks, vq, vs, scale=scale, length=length,
            block_k=tuned_bk, interpret=interpret),
        "bf16": lambda: attn_decode.decode_attention(
            q.astype(jnp.bfloat16), kb, vb, scale=scale, length=length,
            block_k=tuned_bk, interpret=interpret),
    }, reps, trials)

    # Accuracy of the shipped kernel path against the float oracle on the
    # ORIGINAL (pre-quantization) values — this is the quantization error
    # plus any kernel-numerics error, i.e. what serving actually eats.
    out_q = attn_decode_int8.quantized_decode_attention(
        q, kq, ks, vq, vs, scale=scale, length=length, block_k=tuned_bk,
        interpret=interpret)
    out_f = attn_decode.decode_ref(
        q, k[:, :, None, :], v[:, :, None, :], length=length, scale=scale)
    max_abs_err = float(jnp.max(jnp.abs(
        out_q.astype(jnp.float32) - out_f.astype(jnp.float32))))

    bpt_int8 = quantize.bytes_per_token(dh)
    bpt_bf16 = 2 * dh * 2                        # K + V rows at 2 B/elem
    model = registry.get("decode_int8").cost_fn(problem, plan.knobs)
    return {
        "shape": [b * hkv, g, cache_len, dh],
        "length": length,
        "tuned_block_k": tuned_bk,
        "tuned_source": plan.source,
        "tuned_us": slots["int8"],
        "bf16_us": slots["bf16"],
        "bytes_per_token_int8": bpt_int8,
        "bytes_per_token_bf16": bpt_bf16,
        "bytes_ratio": bpt_bf16 / bpt_int8,
        "max_abs_err": max_abs_err,
        "err_budget": INT8_ERR_BUDGET,
        "model_time_us": model["time_s"] * 1e6,
        "interpret": interpret,
    }


def decode_ragged_measured(b: int = 4, hq: int = 4, hkv: int = 2,
                           dh: int = 32, cache_len: int = 256,
                           block_k: int = 64,
                           reps: int = 3, trials: int = 3):
    """Ragged per-slot lengths vs the shared-scalar broadcast through the
    SAME fused decode kernel — the continuous-batching perf claim,
    recorded two ways:

    * ``fetched_speedup``: K/V blocks streamed under the batch-max
      broadcast / blocks streamed with per-row lengths
      (`cost_model.decode_time_model`'s active-prefix accounting) — the
      exact per-row block count the kernel's scalar-prefetch skip
      executes, deterministic on any backend;
    * ``wall_speedup``: interleaved best-of-``trials`` wall-clock of the
      two calls (interpret mode off-TPU dilutes it with grid overhead —
      the block count is the load-bearing number there).

    The ragged lengths are the staggered steady state of a continuous
    batch: slot i at depth ~(2i+1)/(2b) of the cache.
    """
    from repro.kernels.attention import decode as attn_decode

    interpret = jax.default_backend() != "tpu"
    g = hq // hkv
    lengths = [max(1, ((2 * i + 1) * cache_len) // (2 * b))
               for i in range(b)]
    scale = 1.0 / (dh ** 0.5)
    q = jax.random.normal(jax.random.PRNGKey(0), (b, hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, cache_len, hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, cache_len, hkv, dh),
                          jnp.float32)
    len_vec = jnp.asarray(lengths, jnp.int32)

    slots = _interleaved_best_us({
        key: (lambda length=length: attn_decode.gqa_decode_attention(
            q, k, v, scale=scale, length=length, block_k=block_k,
            interpret=interpret))
        for key, length in (("ragged", len_vec), ("broadcast", cache_len))},
        reps, trials)

    problem = {"bkv": b * hkv, "g": g, "cache_len": cache_len, "dh": dh}
    ragged = cost_model.decode_time_model(
        problem["bkv"], g, cache_len, dh, block_k, lengths=lengths)
    broadcast = cost_model.decode_time_model(
        problem["bkv"], g, cache_len, dh, block_k)
    return {
        "shape": [b, hq, hkv, cache_len, dh],
        "lengths": lengths,
        "block_k": block_k,
        "fetched_ragged": ragged["fetched_k"],
        "fetched_broadcast": broadcast["fetched_k"],
        "fetched_speedup": broadcast["fetched_k"] / ragged["fetched_k"],
        "model_speedup": broadcast["time_s"] / ragged["time_s"],
        "ragged_us": slots["ragged"],
        "broadcast_us": slots["broadcast"],
        "wall_speedup": slots["broadcast"] / slots["ragged"],
        "interpret": interpret,
    }


def tuned_vs_fixed_measured(bh: int = 4, seq: int = 256, dh: int = 32,
                            reps: int = 3, trials: int = 3):
    """Wall-clock tuned-vs-fixed at a size where CPU interpret timing is
    feasible; on TPU this measures the real kernel at the same size.
    Interleaved best-of-``trials`` timing, one slot per distinct block pair
    (same discipline as table1_matmul.tuned_vs_fixed_measured)."""
    interpret = jax.default_backend() != "tpu"
    plan = autotune.tune("attention", {"bh": bh, "sq": seq, "sk": seq,
                                       "dh": dh, "causal": True,
                                       "window": None}, jnp.float32)
    tuned = (plan.knobs["block_q"], plan.knobs["block_k"])
    fixed = (min(FIXED_BLOCK, seq), min(FIXED_BLOCK, seq))
    scale = 1.0 / (dh ** 0.5)
    q = jax.random.normal(jax.random.PRNGKey(0), (bh, seq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, seq, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, seq, dh), jnp.float32)

    slots = _interleaved_best_us({
        (bq, bk): (lambda bq=bq, bk=bk: attn_kernel.flash_attention(
            q, k, v, scale=scale, causal=True, block_q=bq, block_k=bk,
            interpret=interpret))
        for (bq, bk) in {tuned, fixed}}, reps, trials)

    tuned_us = slots[tuned]
    return {
        "shape": [bh, seq, seq, dh],
        "tuned_block": list(tuned),
        "tuned_source": plan.source,
        "tuned_us": tuned_us,
        "fixed_block": list(fixed),
        "fixed_us": slots[fixed],
        "speedup_vs_fixed": slots[fixed] / tuned_us,
        "interpret": interpret,
    }


def main(tuned_recs=None, measured_rec=None, skip_rec=None, decode_rec=None,
         ragged_rec=None, int8_rec=None):
    lines = []
    for r in (tuned_recs if tuned_recs is not None else tuned_vs_fixed()):
        bh, sq, sk, dh = r["shape"]
        lines.append(
            f"attn.tuned_{r['arch']}_b{r['batch']}_p{r['prompt']},0.0,"
            f"speedup_model={r['speedup_model']:.3f};"
            f"block={r['tuned_block'][0]}/{r['tuned_block'][1]};"
            f"src={r['tuned_source']}")
    m = measured_rec if measured_rec is not None else tuned_vs_fixed_measured()
    lines.append(
        f"attn.measured_bh{m['shape'][0]}_s{m['shape'][1]},"
        f"{m['tuned_us']:.1f},"
        f"speedup_vs_fixed={m['speedup_vs_fixed']:.3f};"
        f"block={m['tuned_block'][0]}/{m['tuned_block'][1]}")
    s = skip_rec if skip_rec is not None else causal_skip_measured()
    lines.append(
        f"attn.causal_skip_s{s['shape'][1]},{s['skip_us']:.1f},"
        f"kstep_speedup={s['kstep_speedup']:.3f};"
        f"wall_speedup={s['wall_speedup']:.3f};"
        f"block={s['block'][0]}/{s['block'][1]}")
    d = decode_rec if decode_rec is not None else decode_step_measured()
    lines.append(
        f"attn.decode_bkv{d['shape'][0]}_l{d['shape'][2]},"
        f"{d['tuned_us']:.1f},"
        f"speedup_vs_fixed={d['speedup_vs_fixed']:.3f};"
        f"block_k={d['tuned_block_k']};src={d['tuned_source']}")
    rg = ragged_rec if ragged_rec is not None else decode_ragged_measured()
    lines.append(
        f"attn.decode_ragged_b{rg['shape'][0]}_l{rg['shape'][3]},"
        f"{rg['ragged_us']:.1f},"
        f"fetched_speedup={rg['fetched_speedup']:.3f};"
        f"wall_speedup={rg['wall_speedup']:.3f};"
        f"block_k={rg['block_k']}")
    q8 = int8_rec if int8_rec is not None else decode_int8_measured()
    lines.append(
        f"attn.decode_int8_bkv{q8['shape'][0]}_l{q8['shape'][2]},"
        f"{q8['tuned_us']:.1f},"
        f"bytes_ratio={q8['bytes_ratio']:.3f};"
        f"max_abs_err={q8['max_abs_err']:.4f};"
        f"block_k={q8['tuned_block_k']};src={q8['tuned_source']}")
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    print("\n".join(main()))
