import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the system's analogue of the paper's SystemC system-level simulation:
the compiled artifact proves the generated design is coherent (shardings
compose, memory fits) and yields the machine-model numbers (FLOPs, bytes,
collective traffic) the roofline analysis consumes.

Usage:
  python -m repro.launch.dryrun --arch qwen3_14b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every applicable cell
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.configs.shapes import SHAPES, applicable
from repro.core import cost_model, estimate, hlo_stats
from repro.launch import policy, specs, steps
from repro.launch.mesh import make_production_mesh
from repro.optim import adamw
from repro.parallel import sharding as shd

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _mesh(kind: str):
    return make_production_mesh(multi_pod=(kind == "multi"))


def opt_config(cfg) -> adamw.AdamWConfig:
    return adamw.AdamWConfig(moment_dtype=policy.moment_dtype(cfg))


# §Perf hillclimb variants: each is (rules transform, cfg transform,
# train-step kwargs).  "baseline" is the paper-faithful configuration.
VARIANTS = {
    "baseline": {},
    "sp": {"rules": "sequence_parallel"},          # Megatron-style SP
    "bf16grad": {"grad_dtype": "bfloat16"},        # compressed grad sync
    "sp_bf16grad": {"rules": "sequence_parallel",
                    "grad_dtype": "bfloat16"},
    "lowcap": {"cfg": {"capacity_factor": 1.0}},   # tighter MoE capacity
    "sp_lowcap": {"rules": "sequence_parallel",
                  "cfg": {"capacity_factor": 1.0}},
    "sp_bf16grad_lowcap": {"rules": "sequence_parallel",
                           "grad_dtype": "bfloat16",
                           "cfg": {"capacity_factor": 1.0}},
    "bigchunk": {"cfg": {"attn_chunk": 2048}},     # fewer, larger q-chunks
    "dp_only": {"rules": "data_parallel_only"},    # no TP (small models)
    "dp_only_bf16grad": {"rules": "data_parallel_only",
                         "grad_dtype": "bfloat16"},
    # ZeRO-3-style: weights stay sharded in state, attention activations
    # batch-sharded (XLA gathers weights per layer instead of all-reducing
    # activations).  act_rules only — state keeps the base shardings.
    "attn_dp": {"act_rules": "data_parallel_attention"},
    "attn_dp_lowcap": {"act_rules": "data_parallel_attention",
                       "cfg": {"capacity_factor": 1.0}},
    "sp_attn_dp": {"rules": "sequence_parallel",
                   "act_rules": "data_parallel_attention"},
}

_RULE_FNS = {
    "sequence_parallel": shd.sequence_parallel,
    "data_parallel_only": shd.data_parallel_only,
    "data_parallel_attention": shd.data_parallel_attention,
}


def apply_variant(cfg, rules, variant: str):
    """Returns (cfg, act_rules, state_rules, step_kwargs)."""
    spec = VARIANTS[variant]
    state_rules = rules
    if "rules" in spec:  # applies to both activations and state
        rules = _RULE_FNS[spec["rules"]](rules)
        state_rules = rules
    if "act_rules" in spec:
        rules = _RULE_FNS[spec["act_rules"]](rules)
    if "cfg" in spec:
        cfg = dataclasses.replace(cfg, **spec["cfg"])
    kwargs = {}
    if "grad_dtype" in spec:
        kwargs["grad_dtype"] = jnp.bfloat16
    return cfg, rules, state_rules, kwargs


def _lower_step(cfg, shape, mesh, rules, donate: bool = True,
                step_kwargs: dict | None = None, state_rules=None):
    """Build + lower the step for one cell.  Returns (lowered, tokens,
    model_flops).  ``state_rules`` (default = rules) governs param/optimizer
    shardings; ``rules`` governs activations/batch."""
    step_kwargs = step_kwargs or {}
    state_rules = state_rules or rules
    if shape.kind == "decode":
        abs_, sh = specs.decode_specs(cfg, shape, mesh, rules,
                                      state_rules=state_rules)
        step = steps.make_serve_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(sh["params"], sh["cache"], sh["tokens"]),
            out_shardings=(sh["tokens"], sh["cache"]),
            donate_argnums=(1,) if donate else (),
        )
        lowered = jitted.lower(abs_["params"], abs_["cache"], abs_["tokens"])
        tokens = shape.global_batch  # one new token per sequence
        model_flops = cost_model.model_flops_decode(
            cfg.active_param_count(), tokens)
    else:
        opt_cfg = opt_config(cfg)
        state_abs, state_sh = specs.state_shardings(cfg, opt_cfg, mesh,
                                                    state_rules)
        b_abs = specs.batch_specs(cfg, shape)
        b_sh = specs.batch_shardings(cfg, shape, mesh, rules)
        tokens = shape.global_batch * shape.seq_len
        if shape.kind == "train":
            step = steps.make_train_step(cfg, opt_cfg, **step_kwargs)
            jitted = jax.jit(
                step,
                in_shardings=(state_sh, b_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,) if donate else (),
            )
            lowered = jitted.lower(state_abs, b_abs)
            model_flops = cost_model.model_flops_train(
                cfg.active_param_count(), tokens)
        else:  # prefill
            step = steps.make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(state_sh["params"], b_sh))
            lowered = jitted.lower(state_abs["params"], b_abs)
            model_flops = cost_model.model_flops_decode(
                cfg.active_param_count(), tokens)
    return lowered, tokens, model_flops


def _compiled_stats(compiled, chips: int) -> dict:
    """Whole-cluster stats.  cost_analysis() and the HLO dump describe ONE
    device's SPMD program, so totals scale by the chip count."""
    flops, bytes_accessed = hlo_stats.cost_analysis_stats(compiled)
    colls = hlo_stats.collect_collectives(compiled.as_text())
    return {
        "flops": flops * chips,
        "bytes_accessed": bytes_accessed * chips,
        "collective_bytes": float(colls.total_bytes) * chips,
        "collectives": {k: float(v) * chips
                        for k, v in colls.bytes_by_op.items()},
        "collective_counts": dict(colls.count_by_op),
    }


def _probe_layers(cfg) -> tuple[int, int]:
    period = cfg.attn_period if cfg.family == "hybrid" else max(
        cfg.moe_every, 1)
    period = max(period, 1)
    return period, 2 * period


def _scale_stats(s1: dict, s2: dict, l1: int, l2: int, l_full: int) -> dict:
    """Affine extrapolation per statistic: f(L) = f(L1) + (L-L1) * slope."""

    def extrap(a, b):
        slope = (b - a) / (l2 - l1)
        return max(a + (l_full - l1) * slope, 0.0)

    out = {
        "flops": extrap(s1["flops"], s2["flops"]),
        "bytes_accessed": extrap(s1["bytes_accessed"], s2["bytes_accessed"]),
    }
    coll = {}
    for op in set(s1["collectives"]) | set(s2["collectives"]):
        coll[op] = extrap(s1["collectives"].get(op, 0.0),
                          s2["collectives"].get(op, 0.0))
    out["collectives"] = coll
    out["collective_bytes"] = sum(coll.values())
    return out


def probe_cell(cfg, shape, mesh, rules, step_kwargs=None,
               state_rules=None) -> dict:
    """Differential cost probes: compile unrolled L1/L2-layer versions at the
    full input shape and extrapolate per-layer costs to the real depth.
    Needed because XLA cost analysis counts while-loop bodies once."""
    l1, l2 = _probe_layers(cfg)
    stats = []
    for lp in (l1, l2):
        # Unroll the layer stack and the attention q-chunk loop so every op is
        # visible to cost analysis.  The fused loss is lowered UNchunked
        # (identical flops/bytes; unrolling its ~512 token-chunks would
        # explode compile time, and probe memory is never allocated).
        pcfg = dataclasses.replace(cfg, num_layers=lp, scan_layers=False,
                                   probe_unroll=True, loss_chunk=0)
        lowered, _, _ = _lower_step(pcfg, shape, mesh, rules, donate=False,
                                    step_kwargs=step_kwargs,
                                    state_rules=state_rules)
        stats.append(_compiled_stats(lowered.compile(), mesh.size))
    return _scale_stats(stats[0], stats[1], l1, l2, cfg.num_layers)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             probes: bool = True, variant: str = "baseline") -> dict:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}

    mesh = _mesh(mesh_kind)
    rules = specs.rules_for(mesh, shape)
    cfg, rules, state_rules, step_kwargs = apply_variant(cfg, rules, variant)
    chips = mesh.size
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "variant": variant, "chips": chips, "status": "ok"}

    with jax.set_mesh(mesh), shd.use_rules(rules):
        t0 = time.time()
        lowered, tokens, model_flops = _lower_step(
            cfg, shape, mesh, rules, step_kwargs=step_kwargs,
            state_rules=state_rules)
        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

        # Memory proof comes from the real (scanned) compile.
        mem = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            try:
                record[attr] = int(getattr(mem, attr))
            except Exception:
                pass
        record["raw"] = _compiled_stats(compiled, chips)  # undercounted (scan)

        # Compute + collective terms come from the differential probes (+
        # recurrence-interior correction); the memory term from the
        # analytical TPU-path traffic model, with probe HLO bytes kept as the
        # CPU-fusion upper bound (see core/estimate.py).
        pbytes = 2 if policy.param_dtype(cfg) == jnp.bfloat16 else 4
        mbytes = 1.03 if policy.moment_dtype(cfg) == "int8" else 4.0
        bm = estimate.bytes_model(
            cfg, batch=shape.global_batch,
            seq=1 if shape.kind == "decode" else shape.seq_len,
            kind=shape.kind, param_bytes=pbytes, moment_bytes=mbytes,
            cache_len=shape.seq_len if shape.kind == "decode" else 0)
        record["bytes_model"] = bm
        if probes:
            t2 = time.time()
            ext = probe_cell(cfg, shape, mesh, rules, step_kwargs,
                             state_rules)
            record["probe_s"] = round(time.time() - t2, 2)
            rec_f, rec_b = estimate.recurrence_correction(cfg, tokens,
                                                          shape.kind)
            ext["flops"] += rec_f
            ext["bytes_accessed"] += rec_b
            ext["recurrence_correction"] = {"flops": rec_f, "bytes": rec_b}
            record["extrapolated"] = ext
            flops = ext["flops"]
            coll_bytes = ext["collective_bytes"]
        else:
            raw = record["raw"]
            flops = raw["flops"]
            coll_bytes = raw["collective_bytes"]
        bytes_accessed = bm["total"]

        roof = cost_model.roofline(flops, bytes_accessed, coll_bytes,
                                   chips, model_flops=model_flops)
        record.update({"model_flops": model_flops, "tokens": tokens,
                       "roofline": roof.row()})
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--variant", choices=list(VARIANTS), default="baseline")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the differential cost probes (faster)")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args(argv)
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    cells = []
    if args.all:
        for arch in configs.list_archs():
            for shape in SHAPES:
                for mesh_kind in ("single", "multi"):
                    cells.append((arch, shape, mesh_kind))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape, args.mesh))

    failures = 0
    for arch, shape, mesh_kind in cells:
        tag = f"{arch}__{shape}__{mesh_kind}"
        if args.variant != "baseline":
            tag += f"__{args.variant}"
        try:
            rec = run_cell(arch, shape, mesh_kind, probes=not args.no_probes,
                           variant=args.variant)
        except Exception as e:
            rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                   "status": "error", "error": repr(e),
                   "traceback": traceback.format_exc()}
            failures += 1
        (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=2))
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" dominant={r['dominant']}"
                     f" compute={r['compute_s']:.4f}s"
                     f" memory={r['memory_s']:.4f}s"
                     f" coll={r['collective_s']:.4f}s"
                     f" useful={r['useful_fraction']:.2f}"
                     f" (lower {rec['lower_s']}s compile {rec['compile_s']}s)")
        elif status == "skipped":
            extra = f" ({rec['reason']})"
        else:
            extra = f" {rec['error']}"
        print(f"[{status:7s}] {tag}{extra}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
