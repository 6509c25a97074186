"""Step functions: train_step (fwd+bwd+AdamW) and serve_step (decode)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.parallel.loss import cross_entropy, fused_cross_entropy

AUX_WEIGHT = 1e-2


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                    grad_dtype=None):
    """``grad_dtype=jnp.bfloat16`` compresses the gradient all-reduce
    (beyond-paper distributed trick; moments still accumulate in f32)."""

    def train_step(state, batch):
        inputs = {k: v for k, v in batch.items() if k != "labels"}

        def loss_fn(params):
            hidden, _, aux = transformer.forward(cfg, params, inputs,
                                                 return_hidden=True)
            head = params["embed" if cfg.tie_embeddings else "head"]["table"]
            loss, metrics = fused_cross_entropy(
                hidden, head, batch["labels"], chunk=cfg.loss_chunk,
                unroll=cfg.probe_unroll)
            return loss + AUX_WEIGHT * aux, (metrics, aux)

        (total, (metrics, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        if grad_dtype is not None:
            grads = jax.tree.map(lambda g: g.astype(grad_dtype), grads)
        new_params, new_opt, opt_metrics = adamw.update(
            state["params"], grads, state["opt"], opt_cfg)
        out_metrics = {**metrics, **opt_metrics,
                       "total_loss": total, "aux_loss": aux}
        return {"params": new_params, "opt": new_opt}, out_metrics

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        inputs = {k: v for k, v in batch.items() if k != "labels"}
        logits, _, _ = transformer.forward(cfg, params, inputs)
        loss, metrics = cross_entropy(logits, batch["labels"])
        return metrics

    return eval_step


def make_prefill_step(cfg: ModelConfig):
    """Serving prefill: forward over the prompt, no cache mutation needed for
    the dry-run shape (prefill_32k measures the forward itself)."""

    def prefill_step(params, batch):
        inputs = {k: v for k, v in batch.items() if k != "labels"}
        logits, _, _ = transformer.forward(cfg, params, inputs,
                                           last_only=True)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    return prefill_step


def _last_valid_logits(logits, active, s):
    """Final-position logits per slot.  With a (B, S) chunked-prefill
    ``active`` each slot's "final position" is the last one it actually
    wrote (variable-length prompts packed into one chunk); everywhere
    else it is literally the last column."""
    if active is not None and active.ndim == 2:
        idx = jnp.clip(jnp.sum(active, axis=1, dtype=jnp.int32) - 1, 0,
                       s - 1)
        return jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
    return logits[:, -1]


def make_serve_step(cfg: ModelConfig, paged=None):
    """One decode step: new token(s) in, next token + updated cache out.

    ``active`` ((B,) bool, optional) is the ragged continuous-batching
    mask: only active slots write cache rows and advance their per-slot
    ``lengths``; ``None`` advances everyone (the uniform-batch case).
    The same step serves two shapes: S=1 is the decode hot loop, S>1 with
    a one-hot ``active`` is the masked batched prefill that fills exactly
    one slot's cache from depth 0 without touching its neighbours — and a
    (B, S) ``active`` is the chunked prefill that packs several
    variable-length prompts (plus riding decode slots) into one forward.
    ``paged`` (a `runtime.paging.PageSpec`, static) switches the cache to
    the paged pool layout.
    """

    def serve_step(params, cache, tokens, active=None):
        logits, new_cache, _ = transformer.forward(
            cfg, params, {"tokens": tokens}, cache=cache, active=active,
            paged=paged)
        last = _last_valid_logits(logits, active, tokens.shape[1])
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return nxt[:, None], new_cache

    return serve_step


def make_guarded_serve_step(cfg: ModelConfig, paged=None):
    """`make_serve_step` plus the per-slot NaN/Inf logits guard (and the
    chaos logits-poison hook) and the advance of each slot's last token —
    the step the fault-tolerant server runs.

    ``serve_step(params, cache, tokens, active, poison, last_tok, admit)``
    returns ``(out, last_tok, cache)``.  ``out`` is one (B, 2) int32 array,
    all the host needs from the step: column 0 the next token, column 1
    ``ok``, 1 iff the slot's final-position logits are entirely finite.  A
    slot with ``ok`` 0 has a garbage token and maybe a poisoned cache — the
    serve loop quarantines exactly that slot (reset + requeue) while its
    neighbours, whose rows are untouched (per-slot masked writes), keep
    decoding bitwise-identically to a fault-free run.  ``poison`` ((B,)
    bool, chaos-injection only) overwrites a slot's logits with NaN *after*
    the forward, so the guard is exercised without corrupting model state.

    The returned ``last_tok`` ((B, 1) int32) is the input one with each
    advancing slot's token replaced by its next one: a slot that takes
    part in the step (``active``) advances where ``ok`` holds, and an
    ``admit``ted slot ((B,) bool, prefill) takes its first token whatever
    ``ok`` says.  ``last_tok`` defaults to ``tokens``, which is what a
    decode step feeds.  With a (B, S) ``active`` and ``admit`` (the chunked
    prefill) a riding slot — active, not admitted — reads its own
    ``last_tok`` at column 0 in place of whatever ``tokens`` holds there.
    """

    def serve_step(params, cache, tokens, active=None, poison=None,
                   last_tok=None, admit=None):
        if last_tok is None:
            last_tok = tokens[:, -1:]
        if admit is not None and active.ndim == 2:
            ride = active[:, 0] & ~admit
            tokens = tokens.at[:, 0].set(
                jnp.where(ride, last_tok[:, 0], tokens[:, 0]))
        logits, new_cache, _ = transformer.forward(
            cfg, params, {"tokens": tokens}, cache=cache, active=active,
            paged=paged)
        last = _last_valid_logits(logits, active, tokens.shape[1])
        if poison is not None:
            last = jnp.where(poison[:, None], jnp.nan, last)
        ok = jnp.all(jnp.isfinite(last), axis=-1)
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        take = ok
        if active is not None:
            take &= active if active.ndim == 1 else jnp.any(active, axis=1)
        if admit is not None:
            take |= admit
        new_last = jnp.where(take[:, None], nxt[:, None], last_tok)
        out = jnp.stack([nxt, ok.astype(jnp.int32)], axis=1)
        return out, new_last, new_cache

    return serve_step
