"""Mixture-of-Experts with load-balanced dispatch.

Three dispatch implementations, in increasing realism:

- `apply_dense`   — every token through every expert, weighted combine.
                    O(T*E) compute; the correctness oracle for tests.
- `apply_grouped` — single-device sort-based dispatch into a static
                    (E, capacity, D) buffer + batched expert einsum +
                    scatter-add combine.  No collectives; exact modulo
                    capacity drops.
- `apply_sharded` — expert parallelism over the mesh's model axis with
                    explicit `lax.all_to_all` token exchange inside
                    `shard_map` (manual over all axes).  This is the paper's
                    NoC data-movement programming adapted to ICI: tokens are
                    the nonzeros, experts the cores, and capacity absorbs the
                    imbalance exactly like the paper's round-robin nnz law
                    (`core.loadbalance`).

All shapes are static; over-capacity tokens are dropped (combine weight 0),
which the capacity factor makes rare under balanced routing.  Dropped items
scatter to an out-of-bounds index with ``mode="drop"`` so they can never
clobber a kept token's slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.loadbalance import expert_capacity
from repro.models import layers
from repro.parallel.sharding import active_rules

Params = dict


def moe_init(key, cfg, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    return {
        "router": layers._dense_init(ks[0], (d, e), dtype=jnp.float32),
        "w_gate": layers._dense_init(ks[1], (e, d, f), dtype=dtype),
        "w_up": layers._dense_init(ks[2], (e, d, f), dtype=dtype),
        "w_down": layers._dense_init(ks[3], (e, f, d), dtype=dtype),
    }


def moe_param_specs() -> Params:
    return {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", None),
        "w_up": ("experts", "embed", None),
        "w_down": ("experts", None, "embed"),
    }


def route(params: Params, x: jax.Array, cfg):
    """x: (T, D) -> (idx (T,k), weights (T,k), aux_loss scalar)."""
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)           # (T, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    e = cfg.num_experts
    hot = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)    # primary choice
    f_e = jnp.mean(hot, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return idx, weights.astype(x.dtype), aux


def _expert_ffn(params: Params, buf: jax.Array) -> jax.Array:
    """buf: (E, C, D) -> (E, C, D), batched SwiGLU over the expert axis."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(buf.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(buf.dtype))
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(buf.dtype))


# ---------------------------------------------------------------------------
# Dense oracle
# ---------------------------------------------------------------------------

def apply_dense(params: Params, x: jax.Array, cfg):
    """(T, D) -> (T, D); exact (no capacity drops)."""
    t, d = x.shape
    idx, weights, aux = route(params, x, cfg)
    buf = jnp.broadcast_to(x[None], (cfg.num_experts, t, d))
    out_all = _expert_ffn(params, buf)                        # (E, T, D)
    gate = jnp.zeros((t, cfg.num_experts), x.dtype)
    gate = gate.at[jnp.arange(t)[:, None], idx].set(weights)
    out = jnp.einsum("etd,te->td", out_all, gate)
    return out, aux


# ---------------------------------------------------------------------------
# Sort-based grouped dispatch (local)
# ---------------------------------------------------------------------------

def _dispatch_indices(flat_e: jax.Array, num_groups: int, capacity: int):
    """Slot assignment for sorted group dispatch.

    flat_e: (N,) destination group of each item.  Returns (slot (N,), keep
    (N,)).  ``slot`` is unique among kept items; use ``where(keep, slot, OOB)``
    with ``mode='drop'`` when scattering.
    """
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    counts = jnp.bincount(flat_e, length=num_groups)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep_sorted = pos < capacity
    slot_sorted = se.astype(jnp.int32) * capacity + jnp.minimum(pos, capacity - 1)
    inv = jnp.argsort(order, stable=True)  # undo the sort
    return slot_sorted[inv], keep_sorted[inv]


def _scatter_slots(values: jax.Array, slot: jax.Array, keep: jax.Array,
                   num_slots: int, fill) -> jax.Array:
    """values (N,) -> (num_slots,) buffer; dropped items write out of bounds."""
    out = jnp.full((num_slots,), fill, dtype=values.dtype)
    write = jnp.where(keep, slot, num_slots)  # OOB => dropped by mode="drop"
    return out.at[write].set(values, mode="drop")


def apply_grouped(params: Params, x: jax.Array, cfg,
                  capacity: int | None = None):
    """(T, D) -> (T, D) via static (E, C, D) buffers. Single-device exact
    path (modulo drops); also the per-device inner loop of `apply_sharded`."""
    t, d = x.shape
    k, e = cfg.top_k, cfg.num_experts
    if capacity is None:
        capacity = expert_capacity(t, e, k, cfg.capacity_factor)
    idx, weights, aux = route(params, x, cfg)

    flat_e = idx.reshape(-1)                                   # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = weights.reshape(-1)
    slot, keep = _dispatch_indices(flat_e, e, capacity)

    slot_token = _scatter_slots(flat_t, slot, keep, e * capacity, t)
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    buf = x_pad[slot_token].reshape(e, capacity, d)
    out_buf = _expert_ffn(params, buf).reshape(e * capacity, d)

    gathered = out_buf[jnp.where(keep, slot, 0)]               # (T*k, D)
    contrib = gathered * (flat_w * keep.astype(flat_w.dtype))[:, None]
    out = jnp.zeros((t, d), x.dtype).at[flat_t].add(contrib.astype(x.dtype))
    return out, aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch: shard_map + all_to_all over the model axis
# ---------------------------------------------------------------------------

def apply_sharded(params: Params, x: jax.Array, cfg, mesh=None):
    """(B, S, D) -> ((B, S, D), aux) with experts sharded over the model axis.

    Tokens travel to their expert shard and back via two all_to_alls;
    everything else is local.  Falls back to the local grouped path when no
    sharding rules are active (CPU tests).  With a replicated batch (e.g.
    batch=1 decode) every device sources the same tokens, receives the same
    contributions back, and the output stays replicated — still correct.
    """
    rules = active_rules()
    b, s, d = x.shape
    if rules is None or rules.table.get("experts") is None:
        out, aux = apply_grouped(params, x.reshape(b * s, d), cfg)
        return out.reshape(b, s, d), aux

    model_axis = rules.table["experts"][0]
    batch_axes = tuple(rules.table.get("batch") or ())
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    n_shards = mesh.shape[model_axis]
    e = cfg.num_experts
    if e % n_shards:
        raise ValueError(f"{e} experts not divisible by model axis {n_shards}")
    e_loc = e // n_shards

    dp_size = 1
    for a in batch_axes:
        dp_size *= mesh.shape[a]
    if dp_size > 1 and b % dp_size != 0:
        # Batch too small to shard (e.g. batch-1 long-context decode):
        # keep it replicated; the a2a exchange stays correct (see docstring).
        batch_axes, dp_size = (), 1
    # Tokens must also divide across the MODEL axis (sequence-sharded
    # dispatch) or every model rank redundantly routes identical tokens.
    if s % n_shards == 0:
        seq_axes = model_axis          # shard sequence over model
        t_loc = (b // dp_size) * (s // n_shards)
    elif (b // dp_size) % n_shards == 0:
        batch_axes = tuple(batch_axes) + (model_axis,)
        seq_axes = None                # model joins the batch sharding
        t_loc = (b // (dp_size * n_shards)) * s
    else:
        seq_axes = None                # tiny decode: replicate over model
        t_loc = (b // dp_size) * s
    k = cfg.top_k
    c_send = expert_capacity(t_loc * k, n_shards, 1, cfg.capacity_factor)
    c_local = expert_capacity(n_shards * c_send, e_loc, 1, cfg.capacity_factor)

    def local_moe(router_w, w_gate, w_up, w_down, x_loc):
        tl = x_loc.shape[0] * x_loc.shape[1]
        xf = x_loc.reshape(tl, d)
        lp = {"router": router_w}
        idx, weights, aux = route(lp, xf, cfg)
        flat_e = idx.reshape(-1)                                # global expert id
        flat_t = jnp.repeat(jnp.arange(tl, dtype=jnp.int32), k)
        flat_w = weights.reshape(-1)
        dest = flat_e // e_loc                                  # destination shard
        slot, keep = _dispatch_indices(dest, n_shards, c_send)

        n_send = n_shards * c_send
        send_tok = _scatter_slots(flat_t, slot, keep, n_send, tl)
        send_eid = _scatter_slots(flat_e % e_loc, slot, keep, n_send, 0)
        send_valid = _scatter_slots(
            jnp.ones_like(flat_t, dtype=jnp.int32), slot, keep, n_send, 0)
        x_padded = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
        send_x = x_padded[send_tok].reshape(n_shards, c_send, d)

        def a2a(v):
            v = v.reshape(n_shards, c_send, *v.shape[2:]) if v.ndim >= 2 else \
                v.reshape(n_shards, c_send)
            return jax.lax.all_to_all(v, model_axis, split_axis=0, concat_axis=0)

        recv_x = a2a(send_x)                                    # (n_shards, c_send, d)
        recv_eid = a2a(send_eid.reshape(n_shards, c_send))
        recv_valid = a2a(send_valid.reshape(n_shards, c_send))

        # Local grouped expert apply over my e_loc experts.
        r = n_shards * c_send
        rx = recv_x.reshape(r, d)
        re = recv_eid.reshape(r)
        rv = recv_valid.reshape(r).astype(jnp.bool_)
        # Invalid slots go to a phantom group e_loc so they can't consume
        # real experts' capacity; their slots land out of bounds and drop.
        lslot, lkeep = _dispatch_indices(
            jnp.where(rv, re, e_loc), e_loc + 1, c_local)
        lkeep = lkeep & rv
        slot_token = _scatter_slots(
            jnp.arange(r, dtype=jnp.int32), lslot, lkeep, e_loc * c_local, r)
        rx_pad = jnp.concatenate([rx, jnp.zeros((1, d), rx.dtype)], axis=0)
        buf = rx_pad[slot_token].reshape(e_loc, c_local, d)
        outb = _expert_ffn(
            {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, buf
        ).reshape(e_loc * c_local, d)
        back = outb[jnp.where(lkeep, lslot, 0)] * lkeep[:, None].astype(outb.dtype)
        back = back.reshape(n_shards, c_send, d)

        res = a2a(back).reshape(n_send, d)                      # results home again
        safe_slot = jnp.where(keep, slot, 0)
        contrib = res[safe_slot] * (flat_w * keep.astype(flat_w.dtype))[:, None]
        out = jnp.zeros((tl, d), xf.dtype).at[flat_t].add(contrib.astype(xf.dtype))
        axes = tuple(dict.fromkeys(tuple(batch_axes) + (model_axis,)))
        aux = jax.lax.pmean(aux, axis_name=axes if len(axes) > 1 else axes[0])
        return out.reshape(x_loc.shape), aux

    manual = frozenset(batch_axes) | {model_axis}
    batch_spec = P(tuple(batch_axes) if batch_axes else None, seq_axes, None)
    # Replica checking off: the body reduces manually (psum/pmean).
    out, aux = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(P(None, None), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  batch_spec),
        out_specs=(batch_spec, P()),
        axis_names=manual,
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"], x)
    return out, jnp.mean(aux)
