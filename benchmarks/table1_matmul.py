"""Table I analogue: blocked dense matmul efficiency vs configuration.

The paper's Table I sweeps the many-core configuration (16 vs 32 cores,
local-memory size) and reports cycles + GFLOPs + efficiency (measured/peak)
from their SystemC machine model.  Here the configuration axis is the VMEM
tile plan; efficiency comes from the same style of analytical machine model
(`core.cost_model.matmul_time_model`), and the kernel itself is additionally
executed (interpret mode, small sizes) to verify the plan is real.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model, dse, tiling
from repro.core.hardware import TPU_V5E
from repro.kernels import autotune, registry
from repro.kernels.matmul import matmul
from repro.kernels.matmul.ref import matmul_ref

# The paper's Table-I problem sizes (scaled to the TPU regime): the shapes
# the acceptance bar compares tuned-vs-fixed on.
TABLE1_SHAPES = [(4096, 4096, 4096), (8192, 8192, 8192),
                 (16384, 16384, 16384), (8192, 2048, 8192)]


def rows():
    out = []
    # Configuration sweep: the paper's {16 cores/32KB, 32 cores/16KB} becomes
    # {VMEM budget} x {problem size}; eq.2 picks the tile.  Small budgets
    # reproduce the paper's regime where the memory term eats into
    # efficiency (their 84-86%); VMEM-scale budgets saturate compute.
    for vmem_mb, n in [(0.25, 4096), (0.5, 4096), (1, 4096), (2, 4096),
                       (8, 8192), (32, 4096), (64, 8192), (96, 8192),
                       (96, 16384)]:
        t = tiling.solve_tpu(vmem_bytes=int(vmem_mb * 2**20), m=n, n=n, k=n)
        res = cost_model.matmul_time_model(n, n, n, t)
        out.append({
            "name": f"matmul_n{n}_vmem{vmem_mb}MB",
            "tile": f"y{t.y}/x{t.x}/z{t.z}",
            "gflops_model": res["gflops"],
            "efficiency": res["efficiency"],
            "time_model_s": res["time_s"],
        })
    # DSE-autotuned point (paper flow, automated)
    t = dse.autotune_matmul_tile(8192, 8192, 8192)
    res = cost_model.matmul_time_model(8192, 8192, 8192, t)
    out.append({
        "name": "matmul_n8192_dse",
        "tile": f"y{t.y}/x{t.x}/z{t.z}",
        "gflops_model": res["gflops"],
        "efficiency": res["efficiency"],
        "time_model_s": res["time_s"],
    })
    return out


def tuned_vs_fixed():
    """Autotuner vs the fixed eq.2 tile on the Table-1 shapes.

    'fixed' is what blocked_matmul callers used before the engine: the
    closed-form eq.2/solve_tpu tile.  'tuned' goes through the full
    DSE -> (measure) -> cache path.  Both are scored by the same machine
    model.  When the plan was selected analytically the tuner's candidate
    set contains the eq.2 seed, so speedup_model >= 1 by construction; a
    wall-clock-selected plan (source='measured', possible on TPU where the
    Table-1 shapes are measurable) may trade model time for real time —
    then measured_us, not speedup_model, is the evidence.
    """
    recs = []
    for m, n, k in TABLE1_SHAPES:
        fixed = tiling.solve_tpu(m=m, n=n, k=k)
        fixed_res = cost_model.matmul_time_model(m, n, k, fixed)
        problem = {"m": m, "n": n, "k": k}
        plan = autotune.tune("matmul", problem, jnp.bfloat16)
        tuned_res = registry.get("matmul").cost_fn(problem, plan.knobs)
        recs.append({
            "shape": [m, n, k],
            "fixed_tile": [fixed.y, fixed.x, fixed.z],
            "tuned_tile": list(plan.knobs["tile"]),
            "tuned_source": plan.source,
            "tuned_measured_us": plan.measured_us,
            "gflops_fixed_model": fixed_res["gflops"],
            "gflops_tuned_model": tuned_res["gflops"],
            "speedup_model": fixed_res["time_s"] / tuned_res["time_s"],
        })
    return recs


def tuned_vs_fixed_measured(size: int = 256, reps: int = 6, trials: int = 3):
    """Wall-clock comparison at a size where CPU interpret timing is
    feasible; on TPU this measures the real kernels at the same size.

    Two baselines, both real pre-engine callers: 'mxu' is the hardcoded
    128^3 tile the tests/benchmarks executed, 'eq2' is what ``tile=None``
    callers got from the closed-form law (clamped to the problem, so at
    small sizes it may coincide with the tuned tile — then its speedup is
    honestly ~1).  Interpret-mode timing is noisy, so take the best of
    ``trials`` alternating measurements per config."""
    m = n = k = size
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    interpret = jax.default_backend() != "tpu"
    plan = autotune.tune("matmul", {"m": m, "n": n, "k": k}, jnp.float32)
    tuned_tile = tiling.Tile(*plan.knobs["tile"])
    from repro.kernels.matmul.ops import clamp_tile
    baselines = {
        "mxu": tiling.Tile(128, 128, 128),
        "eq2": clamp_tile(tiling.solve_tpu(m=m, n=n, k=k,
                                           dtype_bytes=4), m, n, k),
    }

    # One timing slot per distinct tile (a baseline identical to the tuned
    # tile shares its number — two measurements of the same jitted call
    # would otherwise report drift as speedup), measured interleaved so
    # machine drift hits all configs alike.
    slots = {tuned_tile: float("inf")}
    for t in baselines.values():
        slots.setdefault(t, float("inf"))
    for _ in range(trials):
        for t in slots:
            slots[t] = min(slots[t], autotune.measure(
                lambda t=t: matmul(a, b, tile=t, interpret=interpret,
                                   use_kernel=True), reps=reps))

    tuned_us = slots[tuned_tile]
    out = {
        "shape": [m, n, k],
        "tuned_tile": [tuned_tile.y, tuned_tile.x, tuned_tile.z],
        "tuned_source": plan.source,
        "tuned_us": tuned_us,
        "interpret": interpret,
    }
    for name, t in baselines.items():
        out[f"{name}_tile"] = [t.y, t.x, t.z]
        out[f"{name}_us"] = slots[t]
        out[f"speedup_vs_{name}"] = slots[t] / tuned_us
    return out


def kernel_check(reps: int = 3):
    """Execute the kernel (interpret) and the oracle; report us/call + error."""
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (256, 256), jnp.float32)
    b = jax.random.normal(key, (256, 256), jnp.float32)
    t = tiling.Tile(128, 128, 128)
    out = matmul(a, b, tile=t, interpret=True)
    err = float(jnp.max(jnp.abs(out - matmul_ref(a, b))))
    ref_fn = jax.jit(lambda a, b: matmul_ref(a, b))
    ref_fn(a, b).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        ref_fn(a, b).block_until_ready()
    us = (time.perf_counter() - t0) / reps * 1e6
    return {"name": "matmul_kernel_check_256", "us_per_call": us,
            "max_err": err}


def main(tuned_recs=None):
    lines = []
    for r in rows():
        lines.append(
            f"table1.{r['name']},{r['time_model_s'] * 1e6:.1f},"
            f"eff={r['efficiency']:.3f};gflops={r['gflops_model']:.0f};"
            f"tile={r['tile']}")
    for r in (tuned_recs if tuned_recs is not None else tuned_vs_fixed()):
        m, n, k = r["shape"]
        lines.append(
            f"table1.tuned_m{m}n{n}k{k},0.0,"
            f"speedup_model={r['speedup_model']:.3f};"
            f"tile={'/'.join(map(str, r['tuned_tile']))};"
            f"src={r['tuned_source']}")
    kc = kernel_check()
    lines.append(f"table1.{kc['name']},{kc['us_per_call']:.1f},"
                 f"max_err={kc['max_err']:.2e}")
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    print("\n".join(main()))
