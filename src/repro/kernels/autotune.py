"""Autotuning kernel engine: one generic DSE → measure → cache pipeline.

The paper's §IV flow is: enumerate candidate configurations, *simulate*
each (SystemC machine model), pick the winner, synthesize.  Earlier PRs
closed that loop once per kernel family — and accumulated four parallel
copies of the same pipeline.  This module now holds exactly one:

1. **candidates** — the family's ``KernelSpec.enumerate_candidates``
   ranks feasible configurations under the VMEM budget with the analytic
   model (the "simulate" step, microseconds per point);
2. **measure**    — the top-K survivors are timed on the real backend
   (Pallas on TPU; interpret-mode on CPU for small problems, analytic
   fallback above ``max_measure_elems`` where interpret timing is
   meaningless);
3. **memoize**    — winners land in a unified on-disk JSON cache keyed
   ``family:{spec.key_fn(...)}:v{budget}`` (schema v3; v2 files are
   migrated in place, preserving measured entries).

`tune(spec, problem) -> Plan` and `dispatch(family, *args)` are the only
engine entry points; which families exist is entirely the registry's
business (`kernels/registry.py`).  The legacy per-family
`tune_*`/`tuned_*` functions remain as thin deprecation shims so older
call sites keep working while they migrate.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time
import warnings
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core import hardware, ioutil, tiling
from repro.kernels import registry
from repro.kernels.registry import KernelSpec, Plan
from repro.runtime.faults import KernelDispatchFault

# v3: the declarative KernelSpec registry unified the four per-family
# pipelines and entry formats ({"knobs": ..., "detail": ...} instead of
# family-specific field names).  The *meaning* of a cached winner is
# unchanged from v2, so v2 files are migrated in place (measured entries
# survive, re-shaped under the same family-prefixed keys); files from any
# other version are dropped wholesale (see TuneCache._load) — v1 predates
# block skipping and its winners must never be mis-applied.
ENGINE_VERSION = 3
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

# Above this many total operand elements, CPU interpret-mode timing is both
# glacial and unrepresentative — the analytic ranking decides alone.
MAX_MEASURE_ELEMS = 1 << 22


# ---------------------------------------------------------------------------
# On-disk memo cache
# ---------------------------------------------------------------------------

def default_cache_path() -> pathlib.Path:
    env = os.environ.get(CACHE_ENV)
    if env:
        return pathlib.Path(env)
    return pathlib.Path.home() / ".cache" / "repro" / "autotune.json"


# v2 entries carried family-specific field names; map them onto the v3
# {"knobs", "detail"} shape by key prefix.  Unknown prefixes are dropped
# (there is no family left to interpret them).
_V2_KNOB_FIELDS = {
    "matmul": (("tile",), ()),
    "spmv": (("block_rows", "block_cols"), ("waste",)),
    "attention": (("block_q", "block_k"), ()),
    "decode": (("block_k",), ()),
}


def _migrate_v2_entry(key: str, entry: dict) -> dict | None:
    family = key.split(":", 1)[0]
    fields = _V2_KNOB_FIELDS.get(family)
    if fields is None or not isinstance(entry, dict):
        return None
    knob_names, detail_names = fields
    if any(f not in entry for f in knob_names):
        return None
    return {
        "knobs": {f: entry[f] for f in knob_names},
        "source": entry.get("source", "model"),
        "model_time_s": entry.get("model_time_s", 0.0),
        "measured_us": entry.get("measured_us"),
        "detail": {f: entry[f] for f in detail_names if f in entry},
    }


class TuneCache:
    """Tiny write-through JSON cache: {key: plan-dict}.

    One file per machine (keys embed the backend), loaded lazily and
    rewritten on every put — tuning happens once per shape so write
    amplification is irrelevant, and a plain-text file keeps the cache
    inspectable and diffable.
    """

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = pathlib.Path(path) if path else default_cache_path()
        self._data: dict | None = None
        self.hits = 0
        self.misses = 0

    def _load(self) -> dict:
        if self._data is None:
            raw = None
            try:
                text = self.path.read_text()
            except OSError:
                text = None          # no file yet: a fresh cache, silently
            if text is not None:
                try:
                    raw = json.loads(text)
                except ValueError:
                    # Corrupt JSON (truncated write, disk fault, stray
                    # edit): starting a fresh cache silently would destroy
                    # the evidence AND any measured entries a human might
                    # recover.  Quarantine the file instead and warn.
                    self._quarantine_corrupt()
            if (isinstance(raw, dict) and raw.get("version") == 2
                    and isinstance(raw.get("entries"), dict)):
                # v2 -> v3: same winners, new entry shape.  Measured TPU
                # entries are expensive; migration preserves them instead
                # of dropping the whole file.
                migrated = {}
                for key, entry in raw["entries"].items():
                    new = _migrate_v2_entry(key, entry)
                    if new is not None:
                        migrated[key] = new
                raw = {"version": ENGINE_VERSION, "entries": migrated}
            if not (isinstance(raw, dict)
                    and raw.get("version") == ENGINE_VERSION
                    and isinstance(raw.get("entries"), dict)):
                raw = {"version": ENGINE_VERSION, "entries": {}}
            self._data = raw
        return self._data

    def _quarantine_corrupt(self) -> None:
        corrupt = self.path.with_name(self.path.name + ".corrupt")
        try:
            self.path.replace(corrupt)
        except OSError:
            return               # unrenamable (e.g. read-only fs): move on
        warnings.warn(
            f"autotune cache {self.path} held corrupt JSON; quarantined it "
            f"to {corrupt} and starting a fresh cache", RuntimeWarning,
            stacklevel=3)

    def get(self, key: str) -> dict | None:
        entry = self._load()["entries"].get(key)
        if entry is None:
            self.misses += 1
        else:
            self.hits += 1
        return entry

    def put(self, key: str, value: dict) -> None:
        data = self._load()
        data["entries"][key] = value
        try:
            # Atomic temp+fsync+rename (core.ioutil): a process killed
            # mid-save leaves the previous cache intact instead of a torn
            # file for the next run to quarantine.
            ioutil.atomic_write_json(self.path, data)
        except OSError:
            # An unwritable cache must never take down the compute path;
            # the in-memory entry above still serves this process.
            pass


_default_cache: TuneCache | None = None


def get_cache() -> TuneCache:
    """Process-wide cache bound to the current $REPRO_AUTOTUNE_CACHE."""
    global _default_cache
    path = default_cache_path()
    if _default_cache is None or _default_cache.path != path:
        _default_cache = TuneCache(path)
    return _default_cache


# ---------------------------------------------------------------------------
# Measurement harness
# ---------------------------------------------------------------------------

def measure(fn: Callable[[], jax.Array], reps: int = 3,
            warmup: int = 1) -> float:
    """Median-free best-effort wall timing of ``fn`` in microseconds."""
    for _ in range(max(warmup, 0)):
        fn().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(max(reps, 1)):
        fn().block_until_ready()
    return (time.perf_counter() - t0) / max(reps, 1) * 1e6


def _backend() -> str:
    return jax.default_backend()


def _budget_tag(vmem_bytes: int | None) -> str:
    # The budget shapes the feasible set, so constrained and default
    # tunings must not share cache entries.
    return "dflt" if vmem_bytes is None else str(vmem_bytes)


def cache_key(spec: KernelSpec, problem: dict, dtype_name: str,
              backend: str, vmem_bytes: int | None) -> str:
    """`family:{spec suffix}:v{budget}` — the unified v3 key format."""
    return (f"{spec.name}:{spec.key_fn(problem, dtype_name, backend)}"
            f":v{_budget_tag(vmem_bytes)}")


# ---------------------------------------------------------------------------
# The generic engine
# ---------------------------------------------------------------------------

def tune(
    spec: KernelSpec | str, problem: dict, dtype=jnp.float32, *,
    measure_k: int = 3,
    vmem_bytes: int | None = None,
    max_measure_elems: int = MAX_MEASURE_ELEMS,
    cache: TuneCache | None = None,
    interpret: bool | None = None,
) -> Plan:
    """Pick the family's knobs for ``problem`` via DSE → measure → cache.

    ``measure_k=0`` disables measurement (pure analytic ranking) — used by
    planning paths that must stay fast, e.g. server startup on CPU.
    """
    if isinstance(spec, str):
        spec = registry.get(spec)
    dtype = jnp.dtype(dtype)
    backend = _backend()
    cache = cache or get_cache()
    key = cache_key(spec, problem, dtype.name, backend, vmem_bytes)
    measurable = (measure_k > 0
                  and (backend == "tpu"
                       or spec.measure_elems(problem) <= max_measure_elems))

    hit = cache.get(key)
    if hit is not None and hit.get("poisoned"):
        # A kernel launch with this winner failed at dispatch
        # (`mark_plan_poisoned`): never serve it again — re-run the DSE,
        # and the fresh put below replaces the quarantined entry.
        hit = None
    # An analytic-only entry (e.g. written by serve startup with
    # measure_k=0) is upgraded, not returned, once a measuring caller
    # shows up — otherwise the measure step would be skipped forever.
    if hit is not None and not (measurable and hit.get("source") == "model"):
        return Plan(spec.name, key, dict(problem), dict(hit["knobs"]),
                    "cache", hit["model_time_s"], hit.get("measured_us"),
                    dict(hit.get("detail") or {}))

    ranked = spec.enumerate_candidates(problem, dtype_bytes=dtype.itemsize,
                                       vmem_bytes=vmem_bytes,
                                       top=max(measure_k, 1))
    # Deterministic order + dedupe are the engine's job: score first, the
    # family's declared tie-break second, identical knob sets collapsed
    # (small problems clamp many candidates onto the same point).
    seen, cands = set(), []
    for c in sorted(ranked, key=lambda c: (c.score, spec.tie_break(c.knobs))):
        sig = json.dumps(c.knobs, sort_keys=True)
        if sig not in seen:
            seen.add(sig)
            cands.append(c)

    interpret = (backend != "tpu") if interpret is None else interpret
    best, best_us = None, float("inf")
    if measurable and cands:
        inputs = spec.make_inputs(problem, dtype)
        refused = []
        for c in cands[:measure_k]:
            fn = spec.build_launcher(problem, c.knobs, interpret=interpret)
            try:
                us = measure(lambda fn=fn: fn(*inputs))
            except Exception as e:  # the compiler refused this candidate
                # (e.g. a VMEM overflow the model missed): say so, and
                # measure the rest — a plan is never chosen in silence.
                refused.append((c.knobs, e))
                warnings.warn(
                    f"{spec.name} candidate {c.knobs} failed on {backend} "
                    f"and was not measured: {e!r}", RuntimeWarning,
                    stacklevel=2)
                continue
            if us < best_us:
                best, best_us = c, us
        if best is None:
            raise RuntimeError(
                f"every measured {spec.name} candidate for {key} failed on "
                f"{backend}: {refused}")
    if best is not None:
        chosen, source, measured_us = best, "measured", best_us
    else:
        chosen, source, measured_us = cands[0], "model", None

    detail = {f: chosen.detail[f] for f in spec.detail_keys
              if chosen.detail and f in chosen.detail}
    cache.put(key, {"knobs": chosen.knobs, "source": source,
                    "model_time_s": chosen.score,
                    "measured_us": measured_us, "detail": detail})
    return Plan(spec.name, key, dict(problem), dict(chosen.knobs), source,
                chosen.score, measured_us, detail)


# Chaos-injection hook consulted by `dispatch` just before a kernel launch
# (`runtime.faults.FaultInjector.dispatch_hook` via `install_dispatch_hook`).
# None in production: the hot path pays one None-check.
_dispatch_fault_hook: Callable[[str], None] | None = None


def install_dispatch_hook(hook: Callable[[str], None] | None) -> None:
    """Install (or clear, with None) the kernel-dispatch fault hook."""
    global _dispatch_fault_hook
    _dispatch_fault_hook = hook


def mark_plan_poisoned(key: str, cache: TuneCache | None = None) -> None:
    """Quarantine a cached winner whose kernel launch failed: the entry is
    kept (forensics) but flagged, so the next `tune` of its problem re-runs
    the DSE instead of serving the known-bad knobs."""
    cache = cache or get_cache()
    entry = dict(cache._load()["entries"].get(key) or {})
    entry["poisoned"] = True
    cache.put(key, entry)


def dispatch(family: str, *args, cache: TuneCache | None = None,
             interpret: bool = False, use_kernel: bool | None = None,
             measure_k: int | None = None, **kwargs):
    """Run ``family``'s kernel on ``args`` with its autotuned plan.

    Keeps the repo's dispatch convention: Pallas on TPU (or with
    ``interpret=True`` anywhere), the family's pure-jnp oracle otherwise —
    so CPU callers that never reach the kernel path pay no tuning cost.
    ``measure_k=None`` uses the family's declared default (0 for families
    dispatched inside a jit trace, where wall-clocking is impossible;
    measured winners then come from offline callers through the shared
    cache).

    Graceful degradation under chaos injection: an injected
    `KernelDispatchFault` (the hook installed by `install_dispatch_hook`)
    falls back one-shot to the family's pure-jnp reference path —
    numerically equivalent, just slower — and the plan is marked poisoned
    in the cache so the next tune re-runs the DSE instead of re-serving
    the knobs that just failed.  Any other exception — a kernel the
    compiler refused, a bad shape — propagates: on a chip, a silent jnp
    fallback would hide that the kernel never ran.
    """
    spec = registry.get(family)
    if use_kernel is None:
        use_kernel = interpret or _backend() == "tpu"
    if not use_kernel:
        return spec.reference_fn(*args, **kwargs)
    problem, dtype = spec.problem_fn(*args, **kwargs)
    plan = tune(spec, problem, dtype,
                measure_k=spec.default_measure_k
                if measure_k is None else measure_k,
                cache=cache, interpret=interpret)
    try:
        if _dispatch_fault_hook is not None:
            _dispatch_fault_hook(family)
        return spec.run_fn(plan, *args, interpret=interpret, **kwargs)
    except KernelDispatchFault as e:
        mark_plan_poisoned(plan.key, cache=cache)
        warnings.warn(
            f"kernel dispatch for family '{family}' failed ({e!r}); "
            f"falling back to the jnp reference path and poisoning plan "
            f"{plan.key} for re-tune", RuntimeWarning, stacklevel=2)
        return spec.reference_fn(*args, **kwargs)


# ---------------------------------------------------------------------------
# Deprecated per-family shims
# ---------------------------------------------------------------------------
# Everything below delegates to tune()/dispatch(); the per-family plan
# dataclasses and tune_*/tuned_* signatures are kept only so pre-registry
# call sites keep working.  New code should call the engine directly:
#
#     plan = autotune.tune("attention", {...})
#     out = autotune.dispatch("matmul", a, b, activation="gelu")

@dataclasses.dataclass(frozen=True)
class MatmulPlan:
    tile: tiling.Tile
    source: str                  # "cache" | "measured" | "model"
    model_time_s: float
    measured_us: float | None
    key: str


@dataclasses.dataclass(frozen=True)
class SpmvPlan:
    block_rows: int
    block_cols: int | None       # None -> whole-x-resident kernel
    source: str                  # "cache" | "measured" | "model"
    model_time_s: float
    measured_us: float | None
    waste: float                 # active/fetched input metric at block_rows
    key: str


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    block_q: int
    block_k: int
    source: str                  # "cache" | "measured" | "model"
    model_time_s: float
    measured_us: float | None
    key: str


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    block_k: int
    source: str                  # "cache" | "measured" | "model"
    model_time_s: float
    measured_us: float | None
    key: str


def _attention_key(bh: int, sq: int, sk: int, dh: int, causal: bool,
                   window: int | None, dtype: str, backend: str,
                   vmem_bytes: int | None) -> str:
    """Deprecated: compose `cache_key` with the spec's key_fn instead."""
    return cache_key(registry.get("attention"),
                     {"bh": bh, "sq": sq, "sk": sk, "dh": dh,
                      "causal": causal, "window": window},
                     dtype, backend, vmem_bytes)


def rank_spmv_configs(mat, vmem_bytes: int | None = None,
                      block_rows_cands: Sequence[int] = (8, 16, 32, 64),
                      block_cols_cands: Sequence[int | None] = (None, 256,
                                                                512, 1024,
                                                                2048)):
    """Deprecated: moved to `kernels.spmv.spec.rank_configs`."""
    from repro.kernels.spmv import spec as spmv_spec
    return spmv_spec.rank_configs(mat, vmem_bytes=vmem_bytes,
                                  block_rows_cands=block_rows_cands,
                                  block_cols_cands=block_cols_cands)


def tune_matmul(m: int, n: int, k: int, dtype=jnp.float32, *,
                measure_k: int = 3, vmem_bytes: int | None = None,
                max_measure_elems: int = MAX_MEASURE_ELEMS,
                cache: TuneCache | None = None,
                interpret: bool | None = None) -> MatmulPlan:
    """Deprecated shim over ``tune("matmul", ...)``."""
    p = tune("matmul", {"m": m, "n": n, "k": k}, dtype,
             measure_k=measure_k, vmem_bytes=vmem_bytes,
             max_measure_elems=max_measure_elems, cache=cache,
             interpret=interpret)
    return MatmulPlan(tiling.Tile(*p.knobs["tile"]), p.source,
                      p.model_time_s, p.measured_us, p.key)


def tune_spmv(mat, dtype=jnp.float32, *,
              measure_k: int = 3, vmem_bytes: int | None = None,
              max_measure_elems: int = MAX_MEASURE_ELEMS,
              cache: TuneCache | None = None,
              interpret: bool | None = None) -> SpmvPlan:
    """Deprecated shim over ``tune("spmv", ...)``."""
    p = tune("spmv", {"mat": mat}, dtype, measure_k=measure_k,
             vmem_bytes=vmem_bytes, max_measure_elems=max_measure_elems,
             cache=cache, interpret=interpret)
    return SpmvPlan(p.knobs["block_rows"], p.knobs["block_cols"], p.source,
                    p.model_time_s, p.measured_us,
                    p.detail.get("waste", 0.0), p.key)


def tune_attention(bh: int, sq: int, sk: int, dh: int, dtype=jnp.float32, *,
                   causal: bool = True, window: int | None = None,
                   measure_k: int = 3, vmem_bytes: int | None = None,
                   max_measure_elems: int = MAX_MEASURE_ELEMS,
                   cache: TuneCache | None = None,
                   interpret: bool | None = None) -> AttentionPlan:
    """Deprecated shim over ``tune("attention", ...)``."""
    p = tune("attention", {"bh": bh, "sq": sq, "sk": sk, "dh": dh,
                           "causal": causal, "window": window}, dtype,
             measure_k=measure_k, vmem_bytes=vmem_bytes,
             max_measure_elems=max_measure_elems, cache=cache,
             interpret=interpret)
    return AttentionPlan(p.knobs["block_q"], p.knobs["block_k"], p.source,
                         p.model_time_s, p.measured_us, p.key)


def tune_decode(bkv: int, g: int, cache_len: int, dh: int,
                dtype=jnp.float32, *,
                measure_k: int = 3, vmem_bytes: int | None = None,
                max_measure_elems: int = MAX_MEASURE_ELEMS,
                cache: TuneCache | None = None,
                interpret: bool | None = None) -> DecodePlan:
    """Deprecated shim over ``tune("decode", ...)``."""
    p = tune("decode", {"bkv": bkv, "g": g, "cache_len": cache_len,
                        "dh": dh}, dtype,
             measure_k=measure_k, vmem_bytes=vmem_bytes,
             max_measure_elems=max_measure_elems, cache=cache,
             interpret=interpret)
    return DecodePlan(p.knobs["block_k"], p.source, p.model_time_s,
                      p.measured_us, p.key)


def tuned_matmul(a: jax.Array, b: jax.Array,
                 bias: jax.Array | None = None,
                 activation: str | None = None,
                 interpret: bool = False,
                 use_kernel: bool | None = None,
                 compute_dtype=None, out_dtype=None,
                 cache: TuneCache | None = None) -> jax.Array:
    """Deprecated shim over ``dispatch("matmul", ...)``."""
    return dispatch("matmul", a, b, bias=bias, activation=activation,
                    interpret=interpret, use_kernel=use_kernel,
                    compute_dtype=compute_dtype, out_dtype=out_dtype,
                    cache=cache)


def tuned_spmv(mat, x: jax.Array,
               interpret: bool = False,
               use_kernel: bool | None = None,
               cache: TuneCache | None = None) -> jax.Array:
    """Deprecated shim over ``dispatch("spmv", ...)``."""
    return dispatch("spmv", mat, x, interpret=interpret,
                    use_kernel=use_kernel, cache=cache)


def tuned_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    interpret: bool = False,
                    use_kernel: bool | None = None,
                    measure_k: int = 0,
                    cache: TuneCache | None = None) -> jax.Array:
    """Deprecated shim over ``dispatch("attention", ...)``."""
    return dispatch("attention", q, k, v, causal=causal, window=window,
                    interpret=interpret, use_kernel=use_kernel,
                    measure_k=measure_k, cache=cache)


def tuned_decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 length, interpret: bool = False,
                 use_kernel: bool | None = None,
                 measure_k: int = 0,
                 cache: TuneCache | None = None) -> jax.Array:
    """Deprecated shim over ``dispatch("decode", ...)``."""
    return dispatch("decode", q, k, v, length=length, interpret=interpret,
                    use_kernel=use_kernel, measure_k=measure_k, cache=cache)


# ---------------------------------------------------------------------------
# Model-serving plans
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OpPlan:
    """A tuned Plan bound to a named serving op (e.g. "ffn_up") — the unit
    `plan_for_model` returns and `predict_decode_step_us` consumes."""

    op: str
    plan: Plan

    def record(self) -> dict:
        return {"op": self.op, "problem": dict(self.plan.problem),
                **self.plan.record()}


def plan_for_model(cfg, batch: int, *, prefill_len: int = 0,
                   cache_len: int = 0,
                   kv_dtype=jnp.bfloat16,
                   slot_lengths: Sequence[int] | None = None,
                   cache: TuneCache | None = None,
                   measure_k: int = 0) -> list[OpPlan]:
    """Pre-tune the serving-path kernel shapes of a model config.

    Called by `launch.serve` at server startup so the first request never
    pays the search.  Measurement defaults off (analytic ranking only):
    startup happens on the serving critical path.  Covers the decode-path
    matmuls, — when ``prefill_len`` is given — the prefill flash-attention
    shape, and — when ``cache_len`` is given — the fused decode-attention
    fold, so every registered serving family shares one warmup.  Returns
    typed `OpPlan`s; `.record()` them for logging.

    ``slot_lengths`` (optional) is the workload's steady-state slot-depth
    distribution: the decode plan is then tuned on ``batch`` quantiles of
    it (per-slot active-prefix accounting — a ragged batch prefers a finer
    block_k so shallow slots skip more), and the winner is *pinned* under
    the plain runtime dispatch key so the jitted serve step — whose traced
    problem cannot carry the distribution — actually runs the
    workload-aware block.  Pinning never overwrites a measured entry.
    """
    d, f, v = cfg.d_model, cfg.d_ff or cfg.d_model * 4, cfg.vocab_size
    qkv = max(cfg.num_heads * cfg.head_dim, d) or d
    shapes = [
        ("qkv_proj", batch, qkv, d),
        ("out_proj", batch, d, qkv),
        ("ffn_up", batch, f, d),
        ("ffn_down", batch, d, f),
        ("logits", batch, v, d),
    ]
    plans = []
    for name, m, n, k in shapes:
        plans.append(OpPlan(name, tune(
            "matmul", {"m": m, "n": n, "k": k}, jnp.bfloat16,
            measure_k=measure_k, cache=cache)))
    if prefill_len > 0 and cfg.num_heads:
        plans.append(OpPlan("attn_prefill", tune(
            "attention",
            {"bh": batch * cfg.num_heads, "sq": prefill_len,
             "sk": prefill_len, "dh": cfg.head_dim,
             "causal": cfg.causal, "window": cfg.sliding_window},
            jnp.bfloat16, measure_k=measure_k, cache=cache)))
    if cache_len > 0 and cfg.num_heads and cfg.num_kv_heads:
        # Keyed on the KV-cache dtype the server allocates (`kv_dtype`) —
        # the decode kernel streams the cache, not the activations.  An
        # int8 cache routes to the quantized family instead: its layout
        # is fixed (q8 tag in the key), so the plan keys on the bf16
        # activation dtype the serve loop's q rows carry.
        quantized = jnp.dtype(kv_dtype) == jnp.int8
        family = "decode_int8" if quantized else "decode"
        tune_dtype = jnp.bfloat16 if quantized else kv_dtype
        problem = {"bkv": batch * cfg.num_kv_heads,
                   "g": cfg.num_heads // cfg.num_kv_heads,
                   "cache_len": cache_len, "dh": cfg.head_dim}
        if slot_lengths:
            problem["lengths"] = tuple(
                _quantile_lengths(batch, slot_lengths, cache_len))
        plan = tune(family, problem, tune_dtype, measure_k=measure_k,
                    cache=cache)
        if slot_lengths:
            # Pin the workload-aware winner under the runtime dispatch key
            # (the jit-traced problem has no distribution field), unless a
            # measured winner already owns it.
            run_problem = {k: v for k, v in problem.items()
                           if k != "lengths"}
            spec = registry.get(family)
            cache_obj = cache or get_cache()
            run_key = cache_key(spec, run_problem,
                                jnp.dtype(tune_dtype).name, _backend(), None)
            existing = cache_obj._load()["entries"].get(run_key)
            if existing is None or existing.get("source") == "model":
                # Re-score the pinned knobs at the runtime problem: the
                # entry's model time must describe the key it lives under
                # (batch-max accounting), not the ragged score.
                run_cost = spec.cost_fn(run_problem, plan.knobs)
                cache_obj.put(run_key, {
                    "knobs": dict(plan.knobs), "source": "model",
                    "model_time_s": run_cost["time_s"],
                    "measured_us": None,
                    "detail": {"pinned_from": plan.key}})
        plans.append(OpPlan("attn_decode", plan))
    return plans


def _attn_layer_count(cfg) -> int:
    return sum(1 for l in range(cfg.num_layers) if cfg.is_attn_layer(l))


def _quantile_lengths(batch: int, slot_lengths: Sequence[int],
                      cache_len: int) -> list[int]:
    """Resample a workload slot-depth distribution to ``batch`` evenly
    spaced quantiles (sorted, clamped to the allocated cache) — the
    per-slot lengths a candidate batch is priced at."""
    ls = sorted(max(0, min(int(l), cache_len)) for l in slot_lengths)
    return [ls[((2 * i + 1) * len(ls)) // (2 * batch)] for i in range(batch)]


def predict_decode_step_us(cfg, batch: int, *, cache_len: int,
                           kv_dtype=jnp.bfloat16,
                           lengths: Sequence[int] | None = None,
                           plans: list[OpPlan] | None = None,
                           cache: TuneCache | None = None,
                           block_k: int | None = None,
                           chip: hardware.Chip = hardware.TPU_V5E) -> float:
    """Predicted wall time of one decode step at this batch, from the tuned
    plans' model times.

    The qkv/out projections and the KV-stream term are charged per
    *attention* layer (a hybrid's mamba layers have neither — their mixer
    matmuls are an uncounted approximation), the FFN matmuls per layer, the
    logits matmul once.  The KV stream (`2 * batch * cache_len * kv_dim`
    bf16 bytes per attention layer at `hbm_bw`) is the decode hot loop's
    memory floor.

    ``lengths`` (optional, one valid prefix per slot) prices the KV term
    at the ragged batch's active prefixes — the block-rounded per-row
    stream the fused kernel actually executes — instead of the batch-max
    broadcast that charges every short slot the full ``cache_len``.

    ``block_k`` (optional) overrides the tuned plan's KV block in the
    re-priced term: the paged decode kernel streams one *page* per grid
    step, so a paged server prices the stream at its page size rather
    than the contiguous plan's tuned block.

    ``chip`` supplies the re-priced KV term's constants (the server passes
    the row `hardware.chip_for` found for the device it runs on).
    """
    lengths = lengths or None            # empty == no distribution
    plans = plans if plans is not None else plan_for_model(
        cfg, batch, cache_len=cache_len, kv_dtype=kv_dtype,
        slot_lengths=lengths, cache=cache)
    attn_ops_ = {"qkv_proj", "out_proj"}
    ffn_ops = {"ffn_up", "ffn_down"}
    n_attn = _attn_layer_count(cfg)
    attn_us = sum(p.plan.model_time_us for p in plans if p.op in attn_ops_)
    ffn_us = sum(p.plan.model_time_us for p in plans if p.op in ffn_ops)
    logits_us = sum(p.plan.model_time_us for p in plans if p.op == "logits")
    decode_plan = next((p for p in plans if p.op == "attn_decode"), None)
    if decode_plan is not None:
        # The tuned decode-attention plan prices the KV stream *and* the
        # attention FLOPs at the chosen block_k (including ragged-tail
        # over-fetch) — strictly more faithful than the raw byte floor.
        if lengths is not None:
            # Re-price the tuned block_k on the actual length
            # distribution (the plan itself is tuned at the allocated
            # cache depth — the worst case the kernel must still fit).
            from repro.core import cost_model
            prob = decode_plan.plan.problem
            bk = block_k or decode_plan.plan.knobs["block_k"]
            if jnp.dtype(kv_dtype) == jnp.int8:
                model = cost_model.quantized_decode_time_model(
                    prob["bkv"], prob["g"], prob["cache_len"], prob["dh"],
                    bk, chip=chip, lengths=list(lengths))
            else:
                model = cost_model.decode_time_model(
                    prob["bkv"], prob["g"], prob["cache_len"], prob["dh"],
                    bk, dtype_bytes=jnp.dtype(kv_dtype).itemsize,
                    chip=chip, lengths=list(lengths))
            kv_us = n_attn * model["time_s"] * 1e6
        else:
            kv_us = n_attn * decode_plan.plan.model_time_us
    else:
        streamed = (float(sum(lengths)) if lengths is not None
                    else float(batch * cache_len))
        if jnp.dtype(kv_dtype) == jnp.int8:
            # int8 values + one f32 scale per token per KV head, K and V.
            kv_bytes = 2.0 * streamed * (cfg.kv_dim
                                         + 4 * cfg.num_kv_heads)
        else:
            kv_bytes = (2.0 * streamed * cfg.kv_dim
                        * jnp.dtype(kv_dtype).itemsize)        # K+V stream
        kv_us = n_attn * kv_bytes / chip.hbm_bw * 1e6
    return (n_attn * attn_us + cfg.num_layers * ffn_us + logits_us + kv_us)


def select_serving_batch(
    cfg, *, cache_len: int, prefill_len: int = 0,
    kv_dtype=jnp.bfloat16,
    candidates: Sequence[int] = (1, 2, 4, 8, 16, 32, 64),
    latency_budget_ms: float | None = None,
    slot_lengths: Sequence[int] | None = None,
    cache: TuneCache | None = None,
    pool_pages: int | None = None,
    page_size: int | None = None,
    chip: hardware.Chip = hardware.TPU_V5E,
) -> dict:
    """Sweep candidate batch sizes against the tuned plans' predicted step
    time; pick the batch maximizing predicted decode throughput under the
    latency budget.

    This is the paper's DSE methodology lifted one level: the design knob is
    no longer a kernel tile but the *serving batch*, and the simulator is
    the same analytic machine model the kernel tuner ranks with — so the
    continuous-batching loop's shape is a tuner output, not a hand-picked
    default.  Deterministic: analytic model times only (measured cache
    entries, when present, refine the underlying plans but the sweep itself
    never wall-clocks).  Returns the decision record `launch.serve` logs at
    startup: {"batch", "latency_budget_ms", "sweep": [...]}.

    ``slot_lengths`` (optional) is the workload's steady-state slot-depth
    distribution; each candidate batch is priced at ``b`` evenly spaced
    quantiles of it (per-slot active-prefix accounting) instead of the
    batch-max broadcast that over-charges ragged batches — so a mixed
    16/500-token batch no longer pays 500 everywhere in the sweep.

    ``page_size`` (optional, paged serving) adds the free-page term: each
    candidate's steady-state KV demand in pages is checked against the
    physical pool (``pool_pages``, or the candidate's contiguous
    equivalent when None) — a batch whose page demand overflows the pool
    is infeasible no matter its predicted throughput, and the KV stream
    is re-priced at the page granularity the paged kernel walks.
    """
    slot_lengths = slot_lengths or None   # empty queue == no distribution
    sweep = []
    best = None
    decode_plans = {}
    for b in candidates:
        plans = plan_for_model(cfg, b, prefill_len=prefill_len,
                               cache_len=cache_len, kv_dtype=kv_dtype,
                               slot_lengths=slot_lengths, cache=cache)
        lengths_b = (None if slot_lengths is None
                     else _quantile_lengths(b, slot_lengths, cache_len))
        dp = next((p for p in plans if p.op == "attn_decode"), None)
        # Provenance ("model" cold vs "cache" warm) and wall-clock numbers
        # are volatile across runs, so they are stripped from the record;
        # the kept knobs/model_time_us are reproducible *given the same
        # cache contents* (a measured winner in the shared cache
        # deliberately refines the plan — and hence the sweep — relative
        # to a cold cache).  Full provenance lives in the Server's
        # kernel_plan log.
        if dp is not None:
            rec = dp.record()
            for volatile in ("source", "provenance", "measured_us"):
                rec.pop(volatile, None)
            decode_plans[b] = rec
        else:
            decode_plans[b] = None
        step_us = predict_decode_step_us(cfg, b, cache_len=cache_len,
                                         kv_dtype=kv_dtype, plans=plans,
                                         lengths=lengths_b,
                                         block_k=page_size, chip=chip)
        tok_per_s = b / (step_us * 1e-6)
        feasible = (latency_budget_ms is None
                    or step_us <= latency_budget_ms * 1e3)
        row = {"batch": b, "step_us": step_us,
               "tok_per_s": tok_per_s, "feasible": feasible}
        if lengths_b is not None:
            row["slot_lengths"] = lengths_b
            row["mean_len"] = sum(lengths_b) / len(lengths_b)
        if page_size:
            # free-page term: steady-state page demand at the priced
            # lengths vs the physical pool
            lens = lengths_b if lengths_b is not None else [cache_len] * b
            kv_pages = sum(-(-max(1, l) // page_size) for l in lens)
            pool = pool_pages or b * (-(-cache_len // page_size))
            row["kv_pages"] = kv_pages
            row["pool_pages"] = pool
            row["free_pages"] = max(0, pool - kv_pages)
            row["kv_fits"] = kv_pages <= pool
            row["feasible"] = feasible = feasible and row["kv_fits"]
        sweep.append(row)
        if feasible and (best is None or tok_per_s > best["tok_per_s"]):
            best = sweep[-1]
    if best is None:       # nothing met the budget: least-bad latency wins
        # (but never a batch whose pages overflow the pool — that one
        # cannot be served at all)
        fits = [r for r in sweep if r.get("kv_fits", True)]
        best = min(fits or sweep, key=lambda r: r["step_us"])
    return {"batch": best["batch"],
            "predicted_step_us": best["step_us"],
            "predicted_tok_per_s": best["tok_per_s"],
            "latency_budget_ms": latency_budget_ms,
            "length_model": ("active-prefix" if slot_lengths is not None
                             else "batch-max"),
            "decode_plan": decode_plans[best["batch"]],
            "sweep": sweep}
