"""End-to-end trainer: config -> mesh -> data -> resilient step loop.

Runs for real on CPU with reduced configs (``--smoke``), and is the same code
path the production mesh uses.  Demonstrates: sharded state init, the
deterministic data pipeline, async atomic checkpointing with resume, and the
straggler monitor.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_14b --smoke \
      --steps 30 --batch 8 --seq 64
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.data import DataConfig, make_source
from repro.checkpoint import CheckpointManager
from repro.launch import policy, specs, steps
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.runtime.fault_tolerance import (ResilienceConfig, run_resilient)


def build_state(cfg, opt_cfg, key, mesh, rules):
    """Initialize sharded train state on the mesh."""
    p_pspecs = specs.param_pspecs(cfg, rules, mesh)
    params_abs = specs.abstract_params(cfg)
    opt_abs = specs.abstract_opt_state(params_abs, opt_cfg)
    o_pspecs = specs.opt_pspecs(cfg, params_abs, opt_abs, rules, mesh)
    state_sh = {
        "params": jax.tree.map(
            lambda ps: jax.sharding.NamedSharding(mesh, ps), p_pspecs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
        "opt": jax.tree.map(
            lambda ps: jax.sharding.NamedSharding(mesh, ps), o_pspecs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
    }

    def init_fn(k):
        params = transformer.init(cfg, k, dtype=policy.param_dtype(cfg))
        return {"params": params, "opt": adamw.init_state(params, opt_cfg)}

    init_sharded = jax.jit(init_fn, out_shardings=state_sh)
    return init_sharded(key), state_sh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_14b",
                    choices=configs.list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data", default="synthetic", choices=["synthetic",
                                                            "memmap"])
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 pod mesh (needs 256 devices)")
    args = ap.parse_args(argv)

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    opt_cfg = adamw.AdamWConfig(peak_lr=args.lr, warmup_steps=10,
                                total_steps=args.steps,
                                moment_dtype=policy.moment_dtype(cfg))
    if args.production_mesh:
        mesh = make_production_mesh()
    else:
        n = len(jax.devices())
        mesh = make_host_mesh(data=n, model=1)
    rules = specs.rules_for(mesh).with_sizes(mesh)

    dcfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, kind=args.data, path=args.data_path,
        frontend=cfg.frontend, frontend_dim=cfg.frontend_dim,
        num_patches=min(8, args.seq // 4) if cfg.frontend == "patch" else 0)
    source = make_source(dcfg)

    ckpt = CheckpointManager(Path(args.ckpt_dir) / cfg.name, keep=3)
    train_step = jax.jit(steps.make_train_step(cfg, opt_cfg),
                         donate_argnums=(0,))

    with jax.set_mesh(mesh), shd.use_rules(rules):
        state, state_sh = build_state(cfg, opt_cfg, jax.random.PRNGKey(0),
                                      mesh, rules)
        start_step = 0
        if args.resume and ckpt.latest_step() is not None:
            abs_state = jax.eval_shape(lambda: state)
            state, meta = ckpt.restore(None, abs_state, state_sh)
            start_step = meta["step"]
            print(f"resumed from step {start_step}")

        def batch_fn(step):
            b = source.batch(step, 0, 1)
            return {k: jnp.asarray(v) for k, v in b.items()}

        def on_restore(_step):
            abs_state = jax.eval_shape(lambda: state)
            restored, meta = ckpt.restore(None, abs_state, state_sh)
            print(f"restored from step {meta['step']}")
            return restored, meta["step"]

        t0 = time.time()
        state, history, monitor = run_resilient(
            train_step, state, args.steps, ckpt, batch_fn,
            start_step=start_step,
            config=ResilienceConfig(checkpoint_every=args.ckpt_every),
            on_restore=on_restore)
        wall = time.time() - t0

    losses = [h["loss"] for h in history if "loss" in h]
    print(json.dumps({
        "arch": cfg.name,
        "steps": len(history),
        "wall_s": round(wall, 2),
        "first_loss": round(losses[0], 4) if losses else None,
        "last_loss": round(losses[-1], 4) if losses else None,
        "stragglers": len(monitor.reports),
        "final_ckpt": ckpt.latest_step(),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
