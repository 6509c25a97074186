"""KernelSpec registration for the blocked dense-matmul family.

The family-specific halves of the old `tune_matmul`/`tuned_matmul` pipeline
live here as a declaration: candidate enumeration (the paper's Table-I
sweep, moved out of `core/dse.py`), the `matmul_time_model` cost wrapper,
and the Pallas launcher.  The generic engine in `kernels/autotune.py` does
the rest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import cost_model, dse, hardware, tiling
from repro.kernels import registry
from repro.kernels.matmul import ops as matmul_ops


def rank_tiles(
    m: int, n: int, k: int,
    vmem_bytes: int | None = None,
    dtype_bytes: int = 2,
    align: int = hardware.MXU_DIM,
    top: int = 8,
) -> list[dse.Candidate]:
    """Sweep aligned (y, x) pairs; score with the analytical matmul model.

    This is the paper's Table-I exploration (vary cores/local-mem, simulate,
    rank) compressed to one call.  The eq.2 seed is always included, so the
    top candidate is never worse than the paper's closed form.  The ranking
    is deterministic: candidates are scored by model time with (y, x, z) as
    the tie-break, so equal-cost points always order the same way — this is
    what makes the autotune cache reproducible.  Each returned
    ``Candidate.detail`` carries the concrete ``tiling.Tile`` plus the model
    row (`cost_model.matmul_time_model`).
    """
    chip = hardware.TPU_V5E
    budget = vmem_bytes if vmem_bytes is not None else chip.usable_vmem()

    def evaluate(knobs: dict) -> tuple[float, dict]:
        y, x = knobs["y"], knobs["x"]
        z_budget = tiling.max_depth(y, x, budget, dtype_bytes)
        z = max(align, (min(z_budget, k) // align) * align)
        t = tiling.Tile(y, x, z)
        if t.kernel_vmem_bytes(dtype_bytes) > budget:
            return float("inf"), {}
        res = cost_model.matmul_time_model(m, n, k, t, dtype_bytes=dtype_bytes)
        return res["time_s"], {"tile": t, **res}

    seed = tiling.solve_tpu(budget, dtype_bytes, m=m, n=n, k=k)
    ys = sorted({align, 2 * align, 4 * align, 8 * align, seed.y})
    xs = sorted({align, 2 * align, 4 * align, 8 * align, seed.x})
    space = {"y": [v for v in ys if v <= max(m, align)],
             "x": [v for v in xs if v <= max(n, align)]}
    ranked = dse.explore(space, evaluate, top=max(top, 1))
    ranked = [c for c in ranked if c.detail and "tile" in c.detail]
    ranked.sort(key=lambda c: (c.score, c.detail["tile"].y,
                               c.detail["tile"].x, c.detail["tile"].z))
    if not ranked:
        res = cost_model.matmul_time_model(m, n, k, seed,
                                           dtype_bytes=dtype_bytes)
        ranked = [dse.Candidate({"y": seed.y, "x": seed.x}, res["time_s"],
                                {"tile": seed, **res})]
    return ranked[:top]


def _key_fn(problem: dict, dtype: str, backend: str) -> str:
    return f"{problem['m']}x{problem['n']}x{problem['k']}:{dtype}:{backend}"


def _enumerate(problem: dict, dtype_bytes: int, vmem_bytes: int | None,
               top: int) -> list[dse.Candidate]:
    m, n, k = problem["m"], problem["n"], problem["k"]
    # Over-request so the ENGINE's (score, tie_break) sort performs the
    # authoritative top-cut — the ranker's internal order serves only the
    # standalone deprecated rank_* API.
    ranked = rank_tiles(m, n, k, vmem_bytes=vmem_bytes,
                        dtype_bytes=dtype_bytes, top=max(top, 8))
    # Clamp to the padded problem (small shapes collapse many candidates
    # onto the same effective tile; the engine dedupes by knobs).
    out = []
    for c in ranked:
        t = matmul_ops.clamp_tile(c.detail["tile"], m, n, k)
        out.append(dse.Candidate({"tile": [t.y, t.x, t.z]}, c.score, {}))
    return out


def _cost_fn(problem: dict, knobs: dict, dtype_bytes: int = 2) -> dict:
    return cost_model.matmul_time_model(
        problem["m"], problem["n"], problem["k"],
        tiling.Tile(*knobs["tile"]), dtype_bytes=dtype_bytes)


def _make_inputs(problem: dict, dtype) -> tuple:
    m, n, k = problem["m"], problem["n"], problem["k"]
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    return a.astype(dtype), b.astype(dtype)


def _build_launcher(problem: dict, knobs: dict, interpret: bool):
    tile = tiling.Tile(*knobs["tile"])
    return lambda a, b: matmul_ops.matmul(a, b, tile=tile,
                                          interpret=interpret,
                                          use_kernel=True)


def _problem_fn(a, b, bias=None, activation=None, compute_dtype=None,
                out_dtype=None) -> tuple[dict, object]:
    m, k = a.shape
    _, n = b.shape
    dtype = jnp.dtype(compute_dtype) if compute_dtype is not None else a.dtype
    return {"m": m, "n": n, "k": k}, dtype


def _run_fn(plan: registry.Plan, a, b, *, interpret=False, bias=None,
            activation=None, compute_dtype=None, out_dtype=None):
    return matmul_ops.matmul(a, b, tile=tiling.Tile(*plan.knobs["tile"]),
                             bias=bias, activation=activation,
                             interpret=interpret, use_kernel=True,
                             compute_dtype=compute_dtype,
                             out_dtype=out_dtype)


def _reference_fn(a, b, bias=None, activation=None, compute_dtype=None,
                  out_dtype=None):
    return matmul_ops.matmul(a, b, bias=bias, activation=activation,
                             use_kernel=False, compute_dtype=compute_dtype,
                             out_dtype=out_dtype)


registry.register(registry.KernelSpec(
    name="matmul",
    key_fn=_key_fn,
    enumerate_candidates=_enumerate,
    cost_fn=_cost_fn,
    make_inputs=_make_inputs,
    build_launcher=_build_launcher,
    reference_fn=_reference_fn,
    problem_fn=_problem_fn,
    run_fn=_run_fn,
    measure_elems=lambda p: p["m"] * p["k"] + p["k"] * p["n"]
    + p["m"] * p["n"],
    tie_break=lambda knobs: tuple(knobs["tile"]),
    default_measure_k=3,
    bench_key="matmul_tuned_vs_fixed",
))
