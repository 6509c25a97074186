"""Bring-up smoke test: the serve path end to end on one TPU chip.

Run from the checkout root on a machine with a TPU:

    python chip_smoke.py

It runs in one process and never sets ``JAX_PLATFORMS``.  It exits
non-zero, and prints no result, when JAX finds no TPU or when the repo's
sources are not beside it.  Each phase prints one JSON line with its wall
time (compilation included) and what it checked; any failed check raises,
and the script then exits non-zero.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Phases:

a. device: ``device_kind``, the device count and the jax version;
b. the paper's two workloads on the chip: the tuned (measured) matmul at
   4096x5120x17408 bf16, and SpMV, whole-x and blocked, at a Table II
   size (`benchmarks/table2_spmv.py`), each against its oracle;
c. `repro.launch.serve.main` at the one-chip Qwen3-14B cut
   (``--arch qwen3_14b_1chip``), three times: the contiguous f32 cache,
   ``--kv-dtype int8``, and ``--paged --sched spf``; each serves 4
   requests of 512 prompt tokens and 16 generated tokens, completes all
   of them, and falls back to no jnp path;
d. correctness at the cut: a chunked prefill of two ragged prompts and
   one decode step through the cache on the kernel path, against a
   float32 `jax.numpy` forward under ``default_matmul_precision
   ("highest")``, compared on logits;
e. the jitted serve step lowers to ``tpu_custom_call`` at both the
   prefill and the decode shape.

The tuning cache starts empty at ``.chip_smoke/autotune.json`` in the
checkout (gitignored; the serve logs land beside it); the compilation
cache is `repro.launch.compile_cache`'s.
"""

from __future__ import annotations

import contextlib
import gc
import io
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / ".chip_smoke"
ARCH = "qwen3_14b_1chip"
REQUESTS, PROMPT, GEN = 4, 512, 16
MATMUL = (4096, 5120, 17408)          # (m, k, n): Qwen3-14B's MLP up-proj
SPMV_MATRIX = "Maragal_2"             # two 128-column slabs of x
# The kernel accumulates in f32 and rounds its output to bf16: half a bf16
# ulp is 2^-9 of an element, so 2^-7 of the largest one bounds it with room
# for accumulation order.
MATMUL_TOL = 2.0 ** -7
# f32 throughout (the ELL values, x and the sums), differing from the oracle
# only in summation order.
SPMV_TOL = 1e-4
# max|logits - ref| / max|ref|.  The serve path computes in bf16 with f32
# accumulation (`transformer.forward`'s compute_dtype): unit roundoff 2^-8
# at each of the ~6 roundings a layer makes.  At reduced widths on the CPU
# this path measured 0.011-0.018 against the float32 reference, and
# dropping the attention output moved the logits by 1.6, so 0.05 passes
# bf16 rounding and fails any missing or lower-precision part of the math.
LOGIT_TOL = 0.05


def emit(phase: str, t0: float, **checked) -> None:
    print(json.dumps({"phase": phase, "wall_s": time.perf_counter() - t0,
                      **checked}), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def phase_device():
    import jax

    t0 = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{dev.platform!r}); run this on a machine with a TPU")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch import compile_cache

    OUT.mkdir(parents=True, exist_ok=True)
    tune_cache = OUT / "autotune.json"
    tune_cache.unlink(missing_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(tune_cache)
    emit("a_device", t0, platform=dev.platform, kind=dev.device_kind,
         count=len(devices), jax=jax.__version__,
         autotune_cache=str(tune_cache),
         compile_cache=str(compile_cache.enable()))
    return dev, len(devices)


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import table2_spmv
    from repro.kernels import autotune
    from repro.kernels.matmul.ref import matmul_ref
    from repro.kernels.spmv import pack_csr, spmv

    t0 = time.perf_counter()
    m, k, n = MATMUL
    plan = autotune.tune("matmul", {"m": m, "n": n, "k": k}, jnp.bfloat16)
    require(plan.source == "measured",
            f"matmul plan came from {plan.source!r}, not a measurement")
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (m, k), jnp.bfloat16)
    b = jax.random.normal(kb, (k, n), jnp.bfloat16)
    out = autotune.dispatch("matmul", a, b)
    with jax.default_matmul_precision("highest"):
        ref = matmul_ref(a, b, out_dtype=jnp.float32)
    err = rel_err(out, ref)
    require(out.shape == (m, n) and out.dtype == jnp.bfloat16,
            f"matmul output {out.dtype}{out.shape}")
    require(err <= MATMUL_TOL, f"matmul rel err {err} > {MATMUL_TOL}")
    emit("b_matmul", t0, m=m, k=k, n=n, dtype="bfloat16",
         tile=plan.knobs["tile"], plan_source=plan.source,
         measured_us=plan.measured_us, rel_err=err, tol=MATMUL_TOL)

    t0 = time.perf_counter()
    indptr, indices, data, shape = table2_spmv.synthesize(SPMV_MATRIX)
    mat = pack_csr(indptr, indices, data, shape)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(shape[1]),
                    jnp.float32)
    ref = spmv(mat, x, use_kernel=False)
    checked = {}
    for variant, block_cols in (("whole_x", None), ("blocked", 128)):
        y = spmv(mat, x, block_cols=block_cols, use_kernel=True)
        err = rel_err(y, ref)
        require(y.shape == (shape[0],), f"spmv {variant} shape {y.shape}")
        require(err <= SPMV_TOL, f"spmv {variant} rel err {err} > {SPMV_TOL}")
        checked[variant] = err
    emit("b_spmv", t0, matrix=SPMV_MATRIX, shape=list(shape), nnz=mat.nnz,
         rel_err=checked, tol=SPMV_TOL)


def phase_serve() -> None:
    import jax

    from repro.launch import serve

    runs = {"contiguous_f32": [], "int8": ["--kv-dtype", "int8"],
            "paged_spf": ["--paged", "--sched", "spf"]}
    for name, extra in runs.items():
        t0 = time.perf_counter()
        argv = ["--arch", ARCH, "--requests", str(REQUESTS),
                "--prompt-len", str(PROMPT), "--gen", str(GEN), *extra]
        buf = io.StringIO()
        try:
            with contextlib.redirect_stdout(buf):
                rc = serve.main(argv)
        finally:
            (OUT / f"serve_{name}.log").write_text(buf.getvalue())
        summary = json.loads(buf.getvalue().splitlines()[-1])
        outcomes = summary["outcomes"]
        require(rc == 0, f"serve {name} exited {rc}")
        require(summary["submitted"] == REQUESTS
                and outcomes["completed"] == REQUESTS,
                f"serve {name}: submitted {summary['submitted']}, "
                f"outcomes {outcomes}")
        require(summary["tokens_generated"] == REQUESTS * GEN,
                f"serve {name}: {summary['tokens_generated']} tokens")
        require(summary["kernel_fallbacks"] == 0,
                f"serve {name}: {summary['kernel_fallbacks']} fallbacks")
        gc.collect()
        emit(f"c_serve_{name}", t0, argv=argv, batch=summary["batch"],
             kv_dtype=summary["kv_dtype"], submitted=summary["submitted"],
             completed=outcomes["completed"],
             tokens_generated=summary["tokens_generated"],
             kernel_fallbacks=summary["kernel_fallbacks"],
             decode_steps=summary["decode_steps"],
             serve_loop_wall_s=summary["wall_s"],
             live_bytes_after=sum(a.nbytes for a in jax.live_arrays()))


def phase_correctness_and_hlo() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.configs as configs
    from repro.launch import steps
    from repro.models import transformer

    t0 = time.perf_counter()
    cfg = configs.get(ARCH)
    params = transformer.init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    b = 2
    lens = np.array([PROMPT, PROMPT * 3 // 5])   # two ragged prompts
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                             (b, PROMPT + 1))
    cache = transformer.cache_init(cfg, b, PROMPT + 8, dtype=jnp.float32)
    fwd = jax.jit(lambda p, c, t, a: transformer.forward(
        cfg, p, {"tokens": t}, cache=c, active=a)[:2])
    chunk_active = jnp.asarray(np.arange(PROMPT)[None] < lens[:, None])
    logits_p, cache = fwd(params, cache, jnp.asarray(toks[:, :PROMPT]),
                          chunk_active)
    nxt = np.array([[toks[s, lens[s]]] for s in range(b)])
    logits_d, cache = fwd(params, cache, jnp.asarray(nxt),
                          jnp.ones((b,), bool))
    require(np.array_equal(np.asarray(cache["lengths"]), lens + 1),
            f"cache lengths {np.asarray(cache['lengths'])}")
    ref_fwd = jax.jit(lambda p, t: transformer.forward(
        cfg, p, {"tokens": t}, compute_dtype=jnp.float32)[0][0])
    errs = []
    with jax.default_matmul_precision("highest"):
        for s in range(b):
            n = int(lens[s])
            ref = ref_fwd(params, jnp.asarray(np.append(toks[s, :n],
                                                        nxt[s])[None]))
            errs.append({"slot": s, "prompt": n,
                         "prefill": rel_err(logits_p[s, n - 1], ref[n - 1]),
                         "decode": rel_err(logits_d[s, 0], ref[n])})
    worst = max(max(e["prefill"], e["decode"]) for e in errs)
    require(np.isfinite(worst) and worst <= LOGIT_TOL,
            f"logits rel err {worst} > {LOGIT_TOL}: {errs}")
    emit("d_correctness", t0, arch=ARCH, rel_err=errs, tol=LOGIT_TOL)

    t0 = time.perf_counter()
    step = jax.jit(steps.make_guarded_serve_step(cfg))
    shapes = {"prefill": (jnp.zeros((b, PROMPT), jnp.int32), chunk_active),
              "decode": (jnp.zeros((b, 1), jnp.int32), jnp.ones((b,), bool))}
    counts = {}
    for name, (tokens, active) in shapes.items():
        text = step.lower(params, cache, tokens, active,
                          jnp.zeros((b,), bool)).as_text()
        counts[name] = text.count("tpu_custom_call")
        require(counts[name] > 0, f"no tpu_custom_call in the {name} step")
    emit("e_hlo", t0, tpu_custom_calls=counts)


def main() -> int:
    dev, count = phase_device()
    phase_kernels()
    phase_serve()
    phase_correctness_and_hlo()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
