"""Model assembly: embeddings -> scanned block stack -> head.

One entry point serves every assigned architecture family:

- dense / moe / encoder : uniform layers, `lax.scan` over stacked params
- ssm (RWKV6)           : uniform RWKV layers, same scan
- hybrid (Jamba)        : period-`attn_period` heterogeneous groups; scan over
                          groups, sub-layers unrolled inside the group body

`forward` handles train (cache=None) and decode (cache given, S small).
Decode state is {"blocks": stacked per-layer caches, "index": scalar}.
Layer stacks always scan (compact HLO — a 94-layer model lowers to one loop).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers, moe, rwkv, ssm
from repro.models.config import ModelConfig
from repro.parallel.sharding import constrain

Params = dict

# The dtype `forward` computes in: activations, and every matmul weight it
# casts to the activations' dtype.
COMPUTE_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# Per-layer init / apply / cache-init, keyed by the cfg-static layer kind
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, l: int, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {"ln1": layers.rmsnorm_init(cfg.d_model),
                 "ln2": layers.rmsnorm_init(cfg.d_model)}
    if cfg.family == "ssm":
        p["mixer"] = rwkv.rwkv_time_init(k1, cfg, dtype)
        p["mlp"] = rwkv.rwkv_channel_init(k2, cfg, dtype)
        return p
    if cfg.is_attn_layer(l):
        p["mixer"] = layers.attention_init(k1, cfg, dtype)
    else:
        p["mixer"] = ssm.mamba_init(k1, cfg, dtype)
    if cfg.is_moe_layer(l):
        p["mlp"] = moe.moe_init(k2, cfg, dtype)
    elif cfg.family == "encoder":
        p["mlp"] = layers.gelu_mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    else:
        p["mlp"] = layers.swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _keep_inactive(new_c, old_c, active):
    """Mask a recurrent per-layer cache update: inactive slots keep their
    old state.  Only the SSM/RWKV leaves need this — the attention cache
    is protected at the write itself (inactive slots' scatter rows are
    dropped), and re-masking its full (B, L, Hkv, dh) buffers would
    double the decode hot loop's KV-cache traffic for nothing."""
    if active is None or new_c is None:
        return new_c
    if active.ndim == 2:     # (B, S) chunked mask -> per-slot any()
        active = active.any(axis=1)
    return jax.tree.map(
        lambda n, o: jnp.where(
            active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
        new_c, old_c)


def _layer_apply(p: Params, x, cfg: ModelConfig, l: int, positions,
                 cache: Params | None, lengths, active,
                 prefill: bool = False, pages=None, paged=None):
    """Pre-norm block l.  Returns (x, new_cache, aux).

    ``lengths`` is the per-slot valid cache prefix ((B,) int32) and
    ``active`` the per-slot advance mask ((B,) — or (B, S) for chunked
    prefill) — the ragged continuous-batching contract threaded from the
    serve loop; both are None outside decode.  ``pages``/``paged`` carry
    the shared page table + static PageSpec when the KV cache is paged.
    """
    aux = jnp.zeros((), jnp.float32)
    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.family == "ssm":
        h, new_t = rwkv.rwkv_time_mix(p["mixer"], h, cfg, cache)
        x = x + h
        h2 = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
        h2, new_c = rwkv.rwkv_channel_mix(p["mlp"], h2, cfg, cache)
        x = x + h2
        new_cache = ({**new_t, **new_c} if cache is not None else None)
        return x, _keep_inactive(new_cache, cache, active), aux

    if cfg.is_attn_layer(l):
        # Per-slot write masking happens inside the scatter — no
        # _keep_inactive pass over the KV buffers.
        h, new_mix_cache = layers.attention_apply(
            p["mixer"], h, cfg, positions, cache=cache, lengths=lengths,
            active=active, prefill=prefill, pages=pages, paged=paged)
    else:
        h, new_mix_cache = ssm.mamba_apply(p["mixer"], h, cfg, cache=cache)
        new_mix_cache = _keep_inactive(new_mix_cache, cache, active)
    x = x + h

    h2 = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.is_moe_layer(l):
        h2, aux = moe.apply_sharded(p["mlp"], h2, cfg)
    elif cfg.family == "encoder":
        h2 = layers.gelu_mlp_apply(p["mlp"], h2)
    else:
        h2 = layers.swiglu_apply(p["mlp"], h2)
    x = x + h2
    return x, new_mix_cache, aux


def _layer_cache_init(cfg: ModelConfig, l: int, batch: int, cache_len: int,
                      dtype=jnp.bfloat16, paged=None) -> Params:
    if cfg.family == "ssm":
        return rwkv.rwkv_cache_init(cfg, batch, dtype)
    if cfg.is_attn_layer(l):
        return layers.attention_cache_init(cfg, batch, cache_len, dtype,
                                           paged=paged)
    return ssm.mamba_cache_init(cfg, batch, dtype)


def _stack(dicts: list) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *dicts)


# ---------------------------------------------------------------------------
# Logical sharding specs (mirror the init/cache structures exactly)
# ---------------------------------------------------------------------------

def _mlp_specs(cfg: ModelConfig, l: int):
    if cfg.family == "ssm":
        return rwkv.rwkv_channel_param_specs(cfg)
    if cfg.is_moe_layer(l):
        return moe.moe_param_specs()
    if cfg.family == "encoder":
        return layers.gelu_mlp_param_specs()
    return layers.swiglu_param_specs()


def _layer_specs(cfg: ModelConfig, l: int):
    p = {"ln1": {"scale": (None,)}, "ln2": {"scale": (None,)}}
    if cfg.family == "ssm":
        p["mixer"] = rwkv.rwkv_time_param_specs(cfg)
    elif cfg.is_attn_layer(l):
        p["mixer"] = layers.attention_param_specs(cfg)
    else:
        p["mixer"] = ssm.mamba_param_specs(cfg)
    p["mlp"] = _mlp_specs(cfg, l)
    return p


def _is_axes(x) -> bool:
    return isinstance(x, tuple)


def _prepend_layer_axis(tree):
    return jax.tree.map(lambda axes: (None, *axes), tree, is_leaf=_is_axes)


def param_specs(cfg: ModelConfig):
    """Pytree of logical-axis tuples matching `init`'s structure."""
    specs: dict = {"embed": {"table": ("vocab", "embed")}}
    if cfg.frontend:
        specs["frontend"] = {"proj": (None, "embed")}
    if cfg.family == "hybrid":
        period = cfg.attn_period
        group = {str(i): _layer_specs(cfg, i) for i in range(period)}
        specs["blocks"] = _prepend_layer_axis(group)
    else:
        specs["blocks"] = _prepend_layer_axis(_layer_specs(cfg, 0))
    specs["final_norm"] = {"scale": (None,)}
    if not cfg.tie_embeddings:
        specs["head"] = {"table": ("vocab", "embed")}
    return specs


def _layer_cache_specs(cfg: ModelConfig, l: int, paged=None,
                       quantized: bool = False):
    if cfg.family == "ssm":
        return {"shift_t": ("batch", None, "embed"),
                "wkv": ("batch", "heads", None, None),
                "shift_c": ("batch", None, "embed")}
    if cfg.is_attn_layer(l):
        if paged is not None:
            # Pool axes: (num_pages, page_size, Hkv, dh) — no batch axis;
            # pages are interleaved across slots, so only heads shard.
            specs = {"k": (None, None, "kv_heads", None),
                     "v": (None, None, "kv_heads", None)}
            if quantized:
                # Scale leaves drop the dh axis (one f32 per token row).
                specs["k_scale"] = (None, None, "kv_heads")
                specs["v_scale"] = (None, None, "kv_heads")
            return specs
        specs = {"k": ("batch", "kv_seq", "kv_heads", None),
                 "v": ("batch", "kv_seq", "kv_heads", None)}
        if quantized:
            specs["k_scale"] = ("batch", "kv_seq", "kv_heads")
            specs["v_scale"] = ("batch", "kv_seq", "kv_heads")
        return specs
    return {"conv": ("batch", None, "ff"), "h": ("batch", "ff", None)}


def cache_specs(cfg: ModelConfig, paged=None, kv_dtype=None):
    """Pytree of logical-axis tuples matching `cache_init`'s structure.

    ``kv_dtype`` mirrors `cache_init`'s dtype: int8 caches carry the
    extra per-row scale leaves, so their spec tree must too."""
    quantized = kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8
    if cfg.family == "hybrid":
        period = cfg.attn_period
        group = {str(i): _layer_cache_specs(cfg, i, paged, quantized)
                 for i in range(period)}
        blocks = _prepend_layer_axis(group)
    else:
        blocks = _prepend_layer_axis(
            _layer_cache_specs(cfg, 0, paged, quantized))
    specs = {"blocks": blocks, "index": (), "lengths": ("batch",)}
    if paged is not None:
        specs["pages"] = ("batch", None)
    return specs


# ---------------------------------------------------------------------------
# Init / cache init
# ---------------------------------------------------------------------------

def init(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    """Parameters stored in ``dtype`` where `forward` casts them to the
    activations' dtype (projections, MLP and expert weights, embedding and
    head tables, QKV biases, conv weights, shift mixes), and in f32 where
    it reads them in f32 (norm scales, the router, the SSM/RWKV decay and
    delta leaves).  So a tree stored at ``dtype=COMPUTE_DTYPE`` gives
    bitwise the results of the f32 tree it was rounded from, and the step
    casts no weight."""
    ke, kl, kh, kf = jax.random.split(key, 4)
    params: Params = {"embed": layers.embedding_init(ke, cfg.vocab_size,
                                                     cfg.d_model, dtype)}
    if cfg.frontend:
        params["frontend"] = {
            "proj": layers._dense_init(kf, (cfg.frontend_dim, cfg.d_model),
                                       dtype=dtype)
        }
    keys = jax.random.split(kl, cfg.num_layers)
    if cfg.family == "hybrid":
        period = cfg.attn_period
        groups = [
            {str(i): _layer_init(keys[g * period + i], cfg, g * period + i,
                                 dtype)
             for i in range(period)}
            for g in range(cfg.num_layers // period)
        ]
        params["blocks"] = _stack(groups)
    else:
        params["blocks"] = _stack(
            [_layer_init(k, cfg, 0, dtype) for k in keys])
    params["final_norm"] = layers.rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        params["head"] = layers.embedding_init(kh, cfg.vocab_size,
                                               cfg.d_model, dtype)
    return params


def cache_init(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16, index: int = 0, paged=None) -> Params:
    if cfg.family == "hybrid":
        period = cfg.attn_period
        groups = [
            {str(i): _layer_cache_init(cfg, g * period + i, batch, cache_len,
                                       dtype, paged=paged)
             for i in range(period)}
            for g in range(cfg.num_layers // period)
        ]
        blocks = _stack(groups)
    else:
        blocks = _stack([
            _layer_cache_init(cfg, l, batch, cache_len, dtype, paged=paged)
            for l in range(cfg.num_layers)
        ])
    cache = {"blocks": blocks, "index": jnp.full((), index, jnp.int32),
             "lengths": jnp.full((batch,), index, jnp.int32)}
    if paged is not None:
        # ONE page table for the whole stack: logical page j of slot b is
        # the same pool row in every layer's K and V pool.  -1 = no page
        # assigned; the host-side PageAllocator owns the truth and the
        # server refreshes this device copy after allocation changes.
        cache["pages"] = jnp.full((batch, paged.max_pages), -1, jnp.int32)
    return cache


def _is_pool_leaf(a, paged) -> bool:
    """A stacked paged attention pool leaf: (L, num_pages, page_size, ...)
    — distinguishes the pool K/V from batched SSM/RWKV leaves in hybrid
    stacks."""
    return (a.ndim >= 3 and a.shape[1] == paged.num_pages
            and a.shape[2] == paged.page_size)


def _slot_page_mask(cache: Params, slot: int, paged) -> jax.Array:
    """(num_pages,) bool: pool rows held by ``slot`` per its table row."""
    row = cache["pages"][slot]                            # (max_pages,)
    safe = jnp.clip(row, 0, paged.num_pages - 1)
    return jnp.zeros((paged.num_pages,), bool).at[safe].set(row >= 0)


def cache_reset_slot(cache: Params, slot: int, paged=None) -> Params:
    """Zero one slot's rows across every per-layer cache leaf (KV rows,
    SSM conv tails / states, RWKV shifts) and reset its length to 0.

    A recycled continuous-batching slot must start from a state identical
    to a freshly initialized one: the per-slot length masks already hide
    the stale prefix from attention, but zeroing is the defense in depth
    that makes a refilled slot reproduce single-sequence decode bitwise
    (and resets the recurrent states masking cannot reach).

    Paged: pool leaves have no batch axis, so the slot's rows are the
    pool pages its table row names — those are zeroed and the table row
    cleared to -1 (the host-side allocator frees them separately).
    """
    if paged is not None:
        mask = _slot_page_mask(cache, slot, paged)

        def reset(a):
            if _is_pool_leaf(a, paged):           # (L, num_pages, ps, ...)
                m = mask.reshape((1, -1) + (1,) * (a.ndim - 2))
                return jnp.where(m, 0, a)
            return a.at[:, slot].set(0)           # SSM/RWKV leaves: batched
        return {"blocks": jax.tree.map(reset, cache["blocks"]),
                "index": cache["index"],
                "lengths": cache["lengths"].at[slot].set(0),
                "pages": cache["pages"].at[slot].set(-1)}
    blocks = jax.tree.map(lambda a: a.at[:, slot].set(0), cache["blocks"])
    return {"blocks": blocks, "index": cache["index"],
            "lengths": cache["lengths"].at[slot].set(0)}


def cache_poison_slot(cache: Params, slot: int, paged=None) -> Params:
    """Overwrite one slot's float cache rows with NaN (fault injection:
    a corrupted KV block / recurrent state).

    The chaos harness's `kv_corrupt` fault class: NaN lands in every float
    leaf of the slot's per-layer cache (KV rows, SSM conv/state, RWKV
    shifts) so the next decode step's logits for that slot go non-finite
    and the per-slot guard must quarantine it.  Integer leaves and the
    shared index/lengths bookkeeping are untouched — the fault corrupts
    *data*, not control state, exactly like a flipped HBM block would.
    Paged: the slot's "rows" are the pool pages its table row names.
    """
    if paged is not None:
        mask = _slot_page_mask(cache, slot, paged)

        def poison(a):
            if not jnp.issubdtype(a.dtype, jnp.floating):
                return a
            if _is_pool_leaf(a, paged):
                m = mask.reshape((1, -1) + (1,) * (a.ndim - 2))
                return jnp.where(m, jnp.nan, a)
            return a.at[:, slot].set(jnp.nan)
        return {"blocks": jax.tree.map(poison, cache["blocks"]),
                "index": cache["index"], "lengths": cache["lengths"],
                "pages": cache["pages"]}

    def poison(a):
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return a.at[:, slot].set(jnp.nan)
    return {"blocks": jax.tree.map(poison, cache["blocks"]),
            "index": cache["index"], "lengths": cache["lengths"]}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ModelConfig, params: Params, inputs: dict) -> jax.Array:
    parts = []
    key = "frames" if cfg.frontend == "frame" else "patches"
    if cfg.frontend in ("frame", "patch") and key in inputs:
        # modality frontends feed prompts; decode steps are token-only
        feats = inputs[key]
        parts.append(feats @ params["frontend"]["proj"].astype(feats.dtype))
    if "tokens" in inputs:
        parts.append(layers.embedding_lookup(params["embed"],
                                             inputs["tokens"]))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return constrain(x, "batch", "res_seq", "embed")


def forward(cfg: ModelConfig, params: Params, inputs: dict,
            cache: Params | None = None, compute_dtype=COMPUTE_DTYPE,
            return_hidden: bool = False, last_only: bool = False,
            active: jax.Array | None = None, paged=None):
    """Returns (logits-or-hidden, new_cache, aux_loss).

    ``return_hidden`` skips the unembedding (the caller fuses it into a
    chunked loss); ``last_only`` unembeds only the final position (prefill).
    ``active`` ((B,) bool, decode only) masks which slots advance this
    step: inactive slots neither write cache rows nor move their per-slot
    ``lengths`` — the ragged continuous-batching contract (a masked
    batched prefill is ``active`` = one-hot of the refilled slot).  A
    (B, S) ``active`` is the chunked-prefill generalization: each slot
    writes/advances only its own valid prefix of the packed chunk.
    ``paged`` (a `runtime.paging.PageSpec`, static) marks the cache as
    paged; the shared (B, max_pages) page table rides ``cache["pages"]``
    and is threaded to every attention layer.
    """
    x = _embed_inputs(cfg, params, inputs).astype(compute_dtype)
    b, s, _ = x.shape
    index = cache["index"] if cache is not None else None
    lengths = None
    if cache is not None:
        lengths = cache.get("lengths")
        if lengths is None:          # legacy cache without the vector
            lengths = jnp.full((b,), index, jnp.int32)
        # Per-slot absolute positions: each sequence continues from its
        # own depth (uniform lengths reproduce the old shared `index`).
        positions = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    else:
        positions = jnp.arange(s, dtype=jnp.int32)
    act = None
    if cache is not None and active is not None:
        act = jnp.asarray(active).astype(bool)
    pages = cache.get("pages") if (cache is not None and paged is not None) \
        else None

    blocks = params["blocks"]
    block_caches = cache["blocks"] if cache is not None else None
    decode = cache is not None
    # Serving prefill (the `make_prefill_step` path: forward-only, no
    # gradient) routes attention through the autotuned flash kernel; the
    # flag stays a Python-level static so training keeps the jnp path.
    prefill = last_only and cache is None
    apply_fn = functools.partial(_layer_apply, prefill=prefill,
                                 pages=pages, paged=paged)

    if cfg.family == "hybrid":
        period = cfg.attn_period
        # Per-SUB-layer checkpointing: a period-8 Jamba group holds 7 mamba
        # layers whose scan inputs are large; rematting each sub-layer keeps
        # only one sub-layer's working set live during the group's backward.
        lapply = (jax.checkpoint(apply_fn, static_argnums=(2, 3),
                                 prevent_cse=False)
                  if cfg.remat == "full" and not decode else apply_fn)

        def body(xx, gp, gc):
            new_gc = {}
            aux_tot = jnp.zeros((), jnp.float32)
            for i in range(period):
                lc = gc[str(i)] if decode else None
                xx, nc, aux = lapply(gp[str(i)], xx, cfg, i, positions,
                                     lc, lengths, act)
                aux_tot += aux
                if decode:
                    new_gc[str(i)] = nc
            return xx, (new_gc if decode else 0), aux_tot
    else:

        def body(xx, gp, gc):
            xx, nc, aux = apply_fn(gp, xx, cfg, 0, positions, gc, lengths,
                                   act)
            return xx, (nc if decode else 0), aux

    if cfg.remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)

    if cfg.scan_layers:
        if decode:
            def scan_fn(carry, pc):
                gp, gc = pc
                xx, nc, aux = body(carry, gp, gc)
                return xx, (nc, aux)

            x, (new_caches, auxs) = jax.lax.scan(scan_fn, x,
                                                 (blocks, block_caches))
        else:
            def scan_fn(carry, gp):
                xx, _, aux = body(carry, gp, None)
                return xx, aux

            x, auxs = jax.lax.scan(scan_fn, x, blocks)
            new_caches = None
        aux = jnp.sum(auxs)
    else:
        # Unrolled stack — used by the dry-run's differential cost probes
        # (XLA cost analysis counts while-loop bodies once; unrolled layers
        # are counted fully).
        n = jax.tree.leaves(blocks)[0].shape[0]
        aux = jnp.zeros((), jnp.float32)
        caches_out = []
        for l in range(n):
            gp = jax.tree.map(lambda a: a[l], blocks)
            gc = (jax.tree.map(lambda a: a[l], block_caches)
                  if decode else None)
            x, nc, a = body(x, gp, gc)
            aux = aux + a
            if decode:
                caches_out.append(nc)
        new_caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *caches_out)
                      if decode else None)

    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    new_cache = None
    if cache is not None:
        if act is None:
            adv = s
        elif act.ndim == 2:        # chunked: per-slot valid-position count
            adv = jnp.sum(act, axis=1, dtype=jnp.int32)
        else:
            adv = s * act.astype(jnp.int32)
        new_cache = {"blocks": new_caches, "index": index + s,
                     "lengths": lengths + adv}
        if pages is not None:
            # The table itself only changes host-side (allocation); the
            # device copy rides along unchanged.
            new_cache["pages"] = pages
    if return_hidden:
        return x, new_cache, aux
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    if last_only:
        x = x[:, -1:]
    logits = layers.unembed(head, x)
    return logits, new_cache, aux
