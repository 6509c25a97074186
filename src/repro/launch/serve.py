"""Fault-tolerant batched serving driver: ragged continuous batching with a
request lifecycle, graceful degradation, and a chaos mode.

Requests enter a bounded admission queue (`runtime.lifecycle`) and move
through an enforced state machine (QUEUED → PREFILLING → DECODING →
{COMPLETED, TIMED_OUT, EVICTED, FAILED, REJECTED}); the server packs up to
``--batch`` sequences, prefills each arriving request with a *masked
batched prefill* (only the target slot's cache rows are written, from
depth 0), then decodes with per-slot cache depths — every slot attends
only over its own valid prefix, carried as the cache's ``lengths: (B,)``
vector all the way into the fused decode kernel's scalar-prefetch skip.
Finished slots are zeroed and refilled from the queue (continuous
batching); ``--batch 0`` (the default) asks the autotuner for the batch
(`autotune.select_serving_batch`, priced at quantiles of the workload's
slot-depth distribution under ``--latency-budget-ms``).

The robustness layer on top (see docs/ROBUSTNESS.md):

* a per-slot NaN/Inf logits guard — a poisoned slot is quarantined alone
  (reset + requeued with backoff) while its neighbours keep decoding
  bitwise-identically;
* an injected kernel-dispatch fault falls back one-shot to the jnp
  reference step with the plan marked poisoned for re-tune (a real
  kernel failure propagates);
* per-request deadlines (TTFT and total) and retry-with-backoff, with the
  drain loop failing loudly (lifecycle table) instead of spinning when no
  progress is possible;
* a decode watchdog (`runtime.fault_tolerance.DecodeWatchdog`) comparing
  measured step time against `predict_decode_step_us`;
* ``--chaos --fault-seed N``: a deterministic fault schedule
  (`runtime.faults`) injecting one fault of each class;
* ``--load-trace trace.jsonl``: replay a seeded `runtime.loadgen` trace —
  arrivals fire on a deterministic virtual clock (one predicted
  decode-step of time per loop step), the replay path behind the
  traffic-shaped benchmark `benchmarks/serving_load.py`
  (docs/SERVING_BENCH.md).

The final summary line conserves every submitted request exactly once:
``submitted == completed + timed_out + failed + rejected``.  Runs on CPU
with smoke configs:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \
      --requests 6 --prompt-len 16 --gen 12 [--chaos --fault-seed 0]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import repro.configs as configs
from repro.core import hardware
from repro.kernels import autotune
from repro.launch import compile_cache, steps
from repro.launch.mesh import make_host_mesh
from repro.launch import specs
from repro.launch.scheduler import POLICIES, Scheduler
from repro.models import transformer
from repro.parallel import sharding as shd
from repro.runtime import fault_tolerance, faults, loadgen, paging
from repro.runtime import journal as journal_mod
from repro.runtime import snapshot as snapshot_mod
from repro.runtime.lifecycle import (Lifecycle, Request, State, TERMINAL)

# Exit code of a run killed by an injected crash fault: distinct from both
# success and ordinary failure so the crash-smoke CI job can assert the
# process really died mid-serve before it attempts `serve --resume`.
CRASH_EXIT = 17

# The `jax.monitoring` event of one program being lowered (compiled, or
# loaded from the persistent cache): `serve_loop` counts them.
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Server:
    def __init__(self, cfg, batch: int, max_len: int,
                 prefill_len: int = 0, autotune_kernels: bool = True,
                 slot_lengths=None, injector=None, paged=None,
                 kv_dtype=jnp.float32):
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        # The KV-cache storage dtype (`--kv-dtype`): f32 (default), bf16,
        # or int8 — int8 caches carry per-token-row scale leaves and
        # decode through the quantized kernel family (decode_int8).
        self.kv_dtype = jnp.dtype(kv_dtype)
        # `paged` (a runtime.paging.PageSpec, or None for the contiguous
        # cache) switches the KV cache to the pooled page layout
        # (docs/PAGING.md): every layer shares one physical page pool and
        # the cache carries a per-slot page table the host-side allocator
        # mirrors.  The allocator is the truth; `_sync_pages` pushes its
        # table to the device cache after any alloc/free.
        self.paged = paged
        self.allocator = (paging.PageAllocator(paged, batch)
                          if paged is not None else None)
        # Close the DSE loop before taking traffic: pre-tune the decode-path
        # matmul shapes, the prefill flash-attention shape AND the fused
        # decode-attention fold so the kernel engine's cache is warm
        # (analytic-only here — measurement happens offline / on first TPU
        # run).  `plan_for_model` returns typed OpPlans; they are
        # serialized via `.record()` when logged below.
        # kv_dtype matches the cache_init dtype below — the decode plan is
        # keyed on the dtype the kernel actually streams.
        # `slot_lengths` is the workload's steady-state slot-depth
        # distribution: the decode plan is tuned on its quantiles (and
        # pinned under the runtime dispatch key), so the fused kernel runs
        # the ragged-workload-aware block, not the batch-max one.
        self.kernel_plan = (autotune.plan_for_model(cfg, batch,
                                                    prefill_len=prefill_len,
                                                    cache_len=max_len,
                                                    kv_dtype=self.kv_dtype,
                                                    slot_lengths=slot_lengths)
                            if autotune_kernels else [])
        # Stored in the dtype the step computes in (norm scales and the
        # other leaves it reads in f32 stay f32), so no step re-reads wide
        # weights or writes narrowed copies of them.
        self.params = transformer.init(cfg, jax.random.PRNGKey(0),
                                       dtype=transformer.COMPUTE_DTYPE)
        # Stored parameter bytes by dtype name (the serve summary's
        # `param_bytes`).
        self.param_bytes = {}
        for leaf in jax.tree.leaves(self.params):
            dt = leaf.dtype.name
            self.param_bytes[dt] = self.param_bytes.get(dt, 0) + leaf.nbytes
        # One jitted step for every call.  `serve_step` is called as the
        # decode step, ``(params, cache, tokens, active, poison)``, the
        # signature a wrapper of the decode step sees; prefill and the
        # chunk call the same function as `_admit_step`, with the device
        # `last_tok` and the admitted slots as well.
        self.serve_step = self._admit_step = jax.jit(
            steps.make_guarded_serve_step(cfg, paged=paged))
        # The degradation step: same math forced onto the jnp reference
        # path ($REPRO_DECODE_KERNEL=off at trace time) — built lazily on
        # the first kernel-dispatch fault.
        self._serve_step_ref = None
        self.injector = injector
        self.cache = transformer.cache_init(cfg, batch, max_len,
                                            dtype=self.kv_dtype, paged=paged)
        self.slot_len = np.zeros(batch, np.int32)      # tokens generated
        self.slot_target = np.zeros(batch, np.int32)   # stop length
        self.slot_req = -np.ones(batch, np.int32)      # request id
        self.last_tok = jnp.zeros((batch, 1), jnp.int32)
        self.poison = np.zeros(batch, bool)            # chaos logits-NaN arm
        # Blocking device-to-host reads made by `prefill`, `admit_chunk`
        # and `decode_step`: one per call (`_read`), plus paged mode's read
        # of the cache depths before a decode step or a chunk.
        self.host_reads = 0

    def _read(self, x) -> np.ndarray:
        """One blocking device-to-host read, counted in `host_reads`."""
        self.host_reads += 1
        return np.asarray(x)

    def prefill(self, slot: int, req_id: int, prompt: np.ndarray,
                gen_len: int) -> tuple[bool, int]:
        """Masked batched prefill of one slot: the whole prompt in a single
        forward whose ``active`` mask is the slot's one-hot, so ONLY this
        slot's cache rows are written and only its per-slot length advances
        from depth 0.  (The previous slot-local loop stepped the *shared*
        cache with zero tokens for every other slot, silently polluting
        their KV entries and advancing their depths.)  The recycled slot's
        stale KV/state rows are zeroed first — a refilled slot must be
        indistinguishable from a fresh one.

        Returns ``(ok, first)``: ``ok`` is True iff the slot's first-token
        logits were finite (the per-slot guard), ``first`` the first token
        as a host int, which the step also wrote into ``last_tok``; may
        raise `faults.PrefillInterrupt` in chaos mode *after* the slot
        reset — the interrupted slot is left zeroed, so a caller can simply
        release it and requeue the request.

        Traced as ``serve.prefill`` with the parts ``serve.prefill.prep``
        (slot reset, pages, inputs), ``.launch`` (the jitted step's
        asynchronous dispatch), ``.sync`` (the call's single host read:
        the tokens and the guard in one array, which waits for the device;
        its ``reads`` stat counts it) and ``.post`` (slot bookkeeping);
        `decode_step` and `admit_chunk` are split the same way."""
        with TraceAnnotation("serve.prefill", rid=req_id, slot=slot):
            with TraceAnnotation("serve.prefill.prep"):
                prompt = np.asarray(prompt, np.int32)
                if self.cfg.sliding_window:
                    # The ring buffer keeps at most `window` keys; feeding
                    # more in one masked scatter would alias ring rows. A
                    # fresh slot only ever attends the last `window`
                    # prompt tokens anyway.
                    prompt = prompt[-self.cfg.sliding_window:]
                self.cache = transformer.cache_reset_slot(self.cache, slot,
                                                          paged=self.paged)
                if self.allocator is not None:
                    # Drop any pages a previous occupant left behind
                    # (idempotent), then cover the prompt before the
                    # forward — the masked scatter needs physical rows to
                    # land in.  `ensure` consumes the scheduler's admission
                    # reservation as the pages land.
                    self.allocator.free_slot(slot,
                                             rid=int(self.slot_req[slot]))
                    self.allocator.ensure(slot, prompt.size, rid=req_id)
                    self._sync_pages()
                if self.injector is not None:
                    self.injector.prefill_hook(slot, req_id)   # may raise
                toks = np.zeros((self.batch, prompt.size), np.int32)
                toks[slot] = prompt
                active = np.zeros(self.batch, bool)
                active[slot] = True
                toks, active = jnp.asarray(toks), jnp.asarray(active)
            with TraceAnnotation("serve.prefill.launch"):
                # the slot is admitted: it takes its first token in
                # `last_tok` whatever the guard says
                out, self.last_tok, self.cache = self._admit_step(
                    self.params, self.cache, toks, active, None,
                    self.last_tok, active)
            with TraceAnnotation("serve.prefill.sync", reads=1):
                out = self._read(out)
            with TraceAnnotation("serve.prefill.post"):
                self.slot_len[slot] = 0
                self.slot_target[slot] = gen_len
                self.slot_req[slot] = req_id
            return bool(out[slot, 1]), int(out[slot, 0])

    def can_chunk(self) -> bool:
        """Chunked prefill needs the (B, S) active-mask machinery, which
        only the attention families implement (per-slot valid-prefix
        scatter); the ring-buffer SWA layout and the chaos injector's
        ordinal-keyed prefill faults stay on the one-slot path."""
        return (self.cfg.family in ("dense", "moe") and self.cfg.causal
                and not self.cfg.sliding_window and self.injector is None)

    def admit_chunk(self, admits, step: int = 0):
        """Chunked prefill: pack several variable-length prompts into ONE
        forward, with every in-flight decode slot riding along at column
        0 (its next decode token) — prefill no longer stalls decode.

        ``admits`` is a list of ``(slot, rid, prompt, gen_len)`` for idle
        slots.  Each admitted slot's row carries its prompt left-aligned
        under a (B, S) active mask (only valid columns write cache rows
        and advance the slot's length); a riding decode slot's row is its
        ``last_tok`` at column 0.  The guarded step picks each slot's
        *last valid* logits, so admitted slots get their first token and
        riding slots their next one from the same forward.

        Returns ``(ok_admit, nxt, rode, done, bad)``: per-admitted-slot
        finite-logits verdicts, the (B, 1) host token array (an admitted
        slot's first token, a riding slot's next one), the riding slots,
        and the riding slots that finished / went non-finite this step
        (mirroring `decode_step`'s contract for exactly those slots).  The
        step itself puts each rider's ``last_tok`` at column 0 and
        advances ``last_tok``.  Traced as ``serve.chunk`` with the four
        parts of `prefill`; ``.sync`` is the call's single host read."""
        with TraceAnnotation("serve.chunk", step=step, admitted=len(admits)):
            with TraceAnnotation("serve.chunk.prep"):
                width = max(int(np.asarray(p).size) for _, _, p, _ in admits)
                rode = [s for s in range(self.batch) if self.slot_req[s] >= 0]
                for slot, rid, prompt, _ in admits:
                    self.cache = transformer.cache_reset_slot(
                        self.cache, slot, paged=self.paged)
                    if self.allocator is not None:
                        self.allocator.free_slot(
                            slot, rid=int(self.slot_req[slot]))
                        self.allocator.ensure(slot, np.asarray(prompt).size,
                                              rid=rid)
                if self.allocator is not None:
                    depths = self._read(self.cache["lengths"])
                    for s in rode:             # riding slots grow one token
                        self.allocator.ensure(s, int(depths[s]) + 1,
                                              rid=int(self.slot_req[s]))
                    self._sync_pages()
                # a riding row's column 0 stays 0 here: the step reads
                # the slot's `last_tok` into it
                tokens = np.zeros((self.batch, width), np.int32)
                act = np.zeros((self.batch, width), bool)
                admit = np.zeros(self.batch, bool)
                act[rode, 0] = True
                for slot, _, prompt, _ in admits:
                    p = np.asarray(prompt, np.int32)
                    tokens[slot, :p.size] = p
                    act[slot, :p.size] = True
                    admit[slot] = True
                tokens, act = jnp.asarray(tokens), jnp.asarray(act)
                admit = jnp.asarray(admit)
                # a copy: the transfer may still read the host buffer when
                # the arm is reset after the launch
                poison = jnp.asarray(self.poison.copy())
            with TraceAnnotation("serve.chunk.launch"):
                out, self.last_tok, self.cache = self._admit_step(
                    self.params, self.cache, tokens, act, poison,
                    self.last_tok, admit)
                self.poison[:] = False
            with TraceAnnotation("serve.chunk.sync", reads=1):
                out = self._read(out)
            with TraceAnnotation("serve.chunk.post"):
                nxt, ok = out[:, :1], out[:, 1] != 0
                ok_admit = {}
                for slot, rid, _, gen_len in admits:
                    self.slot_len[slot] = 0
                    self.slot_target[slot] = gen_len
                    self.slot_req[slot] = rid
                    ok_admit[slot] = bool(ok[slot])
                adv = [s for s in rode if ok[s]]
                for s in adv:
                    self.slot_len[s] += 1
                done = [s for s in adv
                        if self.slot_len[s] >= self.slot_target[s]]
                bad = [s for s in rode if not ok[s]]
            return ok_admit, nxt, rode, done, bad

    def restore_slot(self, slot: int, rid: int, prompt, tokens,
                     gen_len: int) -> None:
        """Re-prefill an in-flight request to its exact crash-point state
        (crash recovery, docs/ROBUSTNESS.md).

        ``tokens`` is the request's journaled output (first token +
        decode tokens).  After emitting token m-1 the live server held
        cache = prompt ++ tokens[:-1] with ``last_tok`` = tokens[-1] —
        so one masked batched prefill over that prefix (through the same
        `cache_reset_slot` + one-hot-active path a retry uses) rebuilds
        the KV state, and because decode is teacher-forcing-equivalent,
        its next-token prediction must equal the journaled tokens[-1].
        A mismatch means recovery is NOT deterministic (changed params,
        config drift, a corrupted journal) and raises rather than
        silently serving a diverged continuation."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError(f"restore_slot needs >= 1 journaled token "
                             f"for request {rid}")
        prefix = np.concatenate([np.asarray(prompt, np.int32),
                                 np.asarray(tokens[:-1], np.int32)])
        # Injector hooks stay out of the restore path: a prefill fault
        # schedule is keyed on live prefill ordinals, not recovery work.
        inj, self.injector = self.injector, None
        try:
            ok, predicted = self.prefill(slot, rid, prefix, gen_len)
        finally:
            self.injector = inj
        if not ok or predicted != tokens[-1]:
            raise RuntimeError(
                f"deterministic recovery violated for request {rid}: "
                f"re-prefill of {prefix.size} tokens predicted "
                f"{predicted} (finite={ok}) but the journal recorded "
                f"{tokens[-1]} — params/config drift or a corrupt "
                f"journal; refusing to serve a diverged continuation")
        self.slot_len[slot] = len(tokens) - 1

    # -- crash-tolerance: full-state export / restore -----------------------

    def export_state(self) -> dict:
        """The server's complete mutable state as flat numpy arrays — the
        payload `runtime.snapshot` persists: every cache leaf (KV blocks,
        SSM conv/state, RWKV shifts, per-slot ``lengths``, the legacy
        ``index``) plus the slot bookkeeping vectors."""
        leaves, _ = jax.tree_util.tree_flatten_with_path(self.cache)
        arrays = {"cache" + jax.tree_util.keystr(path): np.asarray(leaf)
                  for path, leaf in leaves}
        arrays["slot_len"] = self.slot_len.copy()
        arrays["slot_target"] = self.slot_target.copy()
        arrays["slot_req"] = self.slot_req.copy()
        arrays["last_tok"] = np.asarray(self.last_tok)
        return arrays

    def restore_state(self, arrays: dict) -> None:
        """Inverse of :meth:`export_state`: load a snapshot's arrays into
        this (same-config, same-batch) server, bitwise.  Shape/dtype
        mismatches mean the snapshot belongs to a different serving
        configuration and raise."""
        leaves, treedef = jax.tree_util.tree_flatten_with_path(self.cache)
        new_leaves = []
        for path, leaf in leaves:
            name = "cache" + jax.tree_util.keystr(path)
            if name not in arrays:
                raise ValueError(f"snapshot missing cache leaf {name!r}")
            a = arrays[name]
            if tuple(a.shape) != tuple(leaf.shape) or a.dtype != leaf.dtype:
                raise ValueError(
                    f"snapshot leaf {name!r} is {a.dtype}{a.shape}, server "
                    f"expects {leaf.dtype}{tuple(leaf.shape)} — snapshot "
                    f"from a different serving configuration")
            new_leaves.append(jnp.asarray(a))
        self.cache = jax.tree_util.tree_unflatten(treedef, new_leaves)
        self.slot_len = np.asarray(arrays["slot_len"], np.int32).copy()
        self.slot_target = np.asarray(arrays["slot_target"], np.int32).copy()
        self.slot_req = np.asarray(arrays["slot_req"], np.int32).copy()
        self.last_tok = jnp.asarray(np.asarray(arrays["last_tok"],
                                               np.int32))
        self.poison[:] = False
        if self.paged is not None:
            # Allocation order is canonical (min-heap), so the restored
            # page table fully determines the allocator state — rebuild
            # it rather than snapshotting it (docs/PAGING.md).
            self.allocator = paging.PageAllocator.adopt(
                self.paged, np.asarray(self.cache["pages"]))

    def release_slot(self, slot: int) -> None:
        """Free a slot and zero its cache rows — quarantine for a poisoned
        slot, plain recycling for a completed one (the zeroing is also done
        by the next prefill; doing it here means a NaN-corrupted slot never
        sits armed in the cache).  In paged mode the slot's pages return
        to the pool and its outstanding reservation is dropped."""
        rid = int(self.slot_req[slot])
        self.slot_req[slot] = -1
        self.cache = transformer.cache_reset_slot(self.cache, slot,
                                                  paged=self.paged)
        if self.allocator is not None:
            self.allocator.free_slot(slot, rid=rid)
            self._sync_pages()

    def _sync_pages(self) -> None:
        """Push the host allocator's page table to the device cache (the
        allocator is the truth; the cache copy is what the kernels read)."""
        self.cache["pages"] = jnp.asarray(self.allocator.table)

    def corrupt_kv(self, slot: int) -> None:
        """Chaos hook: NaN over one slot's KV/state cache rows."""
        self.cache = transformer.cache_poison_slot(self.cache, slot,
                                                   paged=self.paged)

    def decode_step(self, step: int = 0, use_ref: bool = False):
        """One ragged decode step: every occupied slot attends over its own
        valid cache prefix (per-slot ``lengths`` threaded down to the fused
        decode kernel's scalar-prefetch vector); idle slots neither write
        nor advance.

        Returns ``(next_tokens, done_slots, bad_slots)``: ``next_tokens``
        is a (B, 1) host array; ``done`` slots hit their stop length this
        step; ``bad`` slots produced non-finite logits (per-slot guard) —
        their token is discarded, they did not advance (the step advances
        ``last_tok`` only where the guard holds), and the caller must
        quarantine them.  ``use_ref=True`` runs the jnp-reference step
        (kernel-dispatch degradation path).  May raise
        `faults.KernelDispatchFault` in chaos mode.  Traced as
        ``serve.decode`` with the four parts of `prefill`; ``.sync`` is
        the call's single host read, and ``.post`` host bookkeeping
        only."""
        with TraceAnnotation("serve.decode", step=step):
            with TraceAnnotation("serve.decode.prep"):
                if self.injector is not None and not use_ref:
                    self.injector.apply_decode_faults(self, step)  # may raise
                if self.allocator is not None:
                    # Decode-boundary crossing: every occupied slot writes
                    # one token this step — grow its page table to cover
                    # depth + 1 *before* the forward so the scatter has a
                    # physical row.  With reservation-priced admission this
                    # never OOMs; an overcommitted pool raises PageOOM and
                    # the serve loop turns it into an eviction
                    # (backpressure), not a crash.
                    depths = self._read(self.cache["lengths"])
                    grew = False
                    for slot in range(self.batch):
                        if self.slot_req[slot] >= 0:
                            grew |= self.allocator.ensure(
                                slot, int(depths[slot]) + 1,
                                rid=int(self.slot_req[slot]))
                    if grew:
                        self._sync_pages()
                active = jnp.asarray(self.slot_req >= 0)
                poison = jnp.asarray(self.poison.copy())    # see admit_chunk
                step_fn = self._ref_step() if use_ref else self.serve_step
            with TraceAnnotation("serve.decode.launch"):
                out, self.last_tok, self.cache = step_fn(
                    self.params, self.cache, self.last_tok, active, poison)
                self.poison[:] = False
            with TraceAnnotation("serve.decode.sync", reads=1):
                out = self._read(out)
            with TraceAnnotation("serve.decode.post"):
                nxt, ok = out[:, :1], out[:, 1] != 0
                adv = (self.slot_req >= 0) & ok
                self.slot_len[adv] += 1
                done = [s for s in range(self.batch)
                        if adv[s] and self.slot_len[s] >= self.slot_target[s]]
                bad = [s for s in range(self.batch)
                       if self.slot_req[s] >= 0 and not ok[s]]
            return nxt, done, bad

    def _ref_step(self):
        """The jnp-reference serve step, traced with the fused decode
        kernel forced off (env read at trace time — the jitted trace is
        cached, so the env flip is scoped to the first call)."""
        if self._serve_step_ref is None:
            import os
            fn = jax.jit(steps.make_guarded_serve_step(self.cfg,
                                                       paged=self.paged))
            old = os.environ.get("REPRO_DECODE_KERNEL")
            os.environ["REPRO_DECODE_KERNEL"] = "off"
            try:
                # trace now, under the env override
                fn(self.params, self.cache,
                   self.last_tok, jnp.asarray(self.slot_req >= 0),
                   jnp.asarray(self.poison))
            finally:
                if old is None:
                    os.environ.pop("REPRO_DECODE_KERNEL", None)
                else:
                    os.environ["REPRO_DECODE_KERNEL"] = old
            self._serve_step_ref = fn
        return self._serve_step_ref


def serve_loop(server: Server, lc: Lifecycle, *, watchdog=None,
               max_steps: int = 100_000, source=None, journal=None,
               snapshots=None, start_step: int = 0,
               scheduler=None) -> dict:
    """Drain every admitted request to a terminal state.

    ``scheduler`` (optional, `launch.scheduler.Scheduler`) replaces the
    lifecycle's plain FCFS pop with a pluggable admission policy; with a
    paged server it is also the backpressure valve — requests are
    admitted only when the page allocator can cover their predicted
    footprint, and requests that could never fit are REJECTED loudly.

    The loop invariant replacing the old ``while completed < requests``
    spin: it runs while *any* request is non-terminal (or an arrival
    ``source`` still has requests to submit), and every iteration either
    fills a slot, decodes, jumps the virtual clock to the next
    retry-backoff eligibility or arrival, or raises with the lifecycle
    table — no silent no-progress spinning.  Returns loop-level stats for
    the summary (generated token count, steps, kernel fallbacks, and
    ``compiles``: programs lowered while the loop ran).

    Each pass is a ``serve.iter`` profiler span holding ``serve.admit``
    (one per admitted request, with its queue wait), ``serve.emit``,
    ``serve.retire``, ``serve.deadlines``, ``serve.wait`` and
    ``serve.snapshot`` spans and the `Server` calls' own; with no trace
    running they cost well under a microsecond each.  Each `Server` call
    reads the device once, in its ``.sync``, and returns host tokens, so
    ``serve.emit`` indexes numpy arrays and reads nothing from the device.

    ``source`` (optional, see `runtime.loadgen`) is pumped every
    iteration: it submits trace requests whose arrival time has been
    reached on the lifecycle clock.  The loop drives any injected clock
    exposing ``on_step`` with its step counter *before* pumping, filling
    slots, or sweeping deadlines — so a virtual clock (one predicted
    decode-step per loop step) makes arrivals, deadlines, TTFT, and
    per-token latencies fully deterministic.  (Previously an injected
    clock was only ever *read*, never advanced, so chaos/load runs got
    wall-clock — i.e. non-reproducible — TTFT percentiles.)

    Crash tolerance (docs/ROBUSTNESS.md, "Crash recovery"): with a
    ``journal`` (`runtime.journal.Journal`, shared with ``lc.journal``)
    every emitted token is logged write-ahead — durably on disk *before*
    it is appended to the request record — and with ``snapshots``
    (`runtime.snapshot.SnapshotStore`) the full server + lifecycle +
    injector state is checkpointed atomically every ``snapshots.every``
    decode steps, bounding the journal tail a `serve --resume` replays.
    ``start_step`` is the resumed run's virtual-clock origin.  An
    injected `faults.CrashFault` deliberately propagates out of this
    loop: a crash is the one fault the process must NOT absorb.
    """
    step = start_step
    last_snap = start_step
    generated = 0
    emitted = 0              # tokens emitted, first tokens included
    occupied = 0             # slots taking part in this iteration's step
    kernel_fallbacks = 0
    max_concurrent = 0
    kv_pages_peak = 0
    kv_peak = None           # allocator utilization snapshot at the peak
    kv_ooms = 0
    chunked_prefills = 0
    compiles = 0
    t_start = time.monotonic()

    def note_kv() -> None:
        nonlocal kv_pages_peak, kv_peak
        a = server.allocator
        if a is not None and a.allocated_pages >= kv_pages_peak:
            kv_pages_peak = a.allocated_pages
            kv_peak = a.utilization()
    first_new_token_s = None
    tick = getattr(lc.clock, "on_step", None)

    def emit(req, tok: int) -> None:
        """Write-ahead token emission: journal first, then append (the
        externally visible effect)."""
        nonlocal first_new_token_s, emitted
        if journal is not None:
            journal.token(req.rid, len(req.tokens), tok, step)
        req.tokens.append(tok)
        emitted += 1
        if first_new_token_s is None:
            first_new_token_s = time.monotonic() - t_start

    def take_snapshot() -> None:
        nonlocal last_snap
        arrays = server.export_state()
        meta = {
            "step": step,
            "lifecycle": snapshot_mod.lifecycle_state(lc),
            "injector": (server.injector.state()
                         if server.injector is not None else None),
        }
        path = snapshots.save(step=step, arrays=arrays, meta=meta,
                              journal_seq=(journal.seq if journal is not None
                                           else 0))
        if journal is not None:
            journal.snapshot(step, path.name)
        last_snap = step

    def pending() -> bool:
        return (lc.open_count() > 0
                or (source is not None and not source.exhausted()))

    def admit_span(req, slot: int):
        """``serve.admit``: one admitted request; ``wait_ms`` is its time
        in the queue, from submission to the start of its admission."""
        return TraceAnnotation("serve.admit", rid=req.rid, slot=slot,
                               wait_ms=(lc.clock() - req.submit_t) * 1e3)

    def count_compile(event: str, duration: float, **kw) -> None:
        nonlocal compiles
        if event == COMPILE_EVENT:
            compiles += 1

    def iterate() -> bool:
        """One pass of the loop (a ``serve.iter`` span); False once
        nothing is pending after the deadline sweep."""
        nonlocal step, generated, kernel_fallbacks, max_concurrent, \
            kv_ooms, chunked_prefills, occupied
        if tick is not None:
            tick(step)
        if source is not None:
            source.pump(lc, step)
        if step > max_steps:
            raise RuntimeError(
                f"serve loop exceeded {max_steps} steps without draining; "
                f"lifecycle table:\n{lc.table()}")
        # -- periodic snapshot (crash-tolerance checkpoint) -----------------
        if snapshots is not None and snapshots.due(step, last_snap):
            with TraceAnnotation("serve.snapshot"):
                take_snapshot()
        # -- fill idle slots from the admission queue -----------------------
        admits = []
        for slot in range(server.batch):
            if server.slot_req[slot] >= 0:
                continue
            req = (scheduler.pop_ready(lc, step) if scheduler is not None
                   else lc.pop_ready(step))
            if req is None:
                break
            admits.append((slot, req))
        chunk = None
        if len(admits) > 1 and server.can_chunk():
            # Chunked prefill: every admitted prompt — plus each in-flight
            # decode slot's next token — packed into ONE forward, so a
            # burst of arrivals costs one step instead of stalling decode
            # behind per-request prefills.
            for slot, req in admits:
                with admit_span(req, slot):
                    lc.transition(req, State.PREFILLING, step)
            ok_admit, c_nxt, c_rode, c_done, c_bad = server.admit_chunk(
                [(slot, req.rid, req.prompt, req.gen_len)
                 for slot, req in admits], step)
            chunked_prefills += 1
            with TraceAnnotation("serve.emit"):
                for slot, req in admits:
                    if not ok_admit[slot]:
                        server.release_slot(slot)
                        lc.evict(req, step, reason="nan_prefill")
                        continue
                    emit(req, int(c_nxt[slot, 0]))
                    lc.record_first_token(req)
                    lc.transition(req, State.DECODING, step)
            chunk = (c_nxt, c_rode, c_done, c_bad)
        else:
            for slot, req in admits:
                with admit_span(req, slot):
                    lc.transition(req, State.PREFILLING, step)
                    try:
                        ok, first = server.prefill(slot, req.rid,
                                                   req.prompt, req.gen_len)
                    except faults.PrefillInterrupt:
                        # the slot was reset before the interrupt: release
                        server.release_slot(slot)
                        if server.allocator is not None:
                            server.allocator.release_reservation(req.rid)
                        lc.evict(req, step, reason="prefill_interrupt")
                        continue
                    except paging.PageOOM:
                        # Defensive: admission reservations normally cover
                        # the prompt; an overcommitted pool requeues the
                        # request (backpressure), never crashes the server.
                        kv_ooms += 1
                        server.release_slot(slot)
                        if server.allocator is not None:
                            server.allocator.release_reservation(req.rid)
                        lc.evict(req, step, reason="kv_oom")
                        continue
                    if not ok:
                        server.release_slot(slot)
                        lc.evict(req, step, reason="nan_prefill")
                        continue
                    emit(req, first)
                    lc.record_first_token(req)
                    lc.transition(req, State.DECODING, step)
        occupied = int((server.slot_req >= 0).sum())
        max_concurrent = max(max_concurrent, occupied)
        note_kv()
        # -- deadline sweep -------------------------------------------------
        with TraceAnnotation("serve.deadlines"):
            for req in lc.check_deadlines(step):
                tslot = np.nonzero(server.slot_req == req.rid)[0]
                if tslot.size:
                    server.release_slot(int(tslot[0]))
        if not pending():
            return False
        # -- progress check -------------------------------------------------
        live = server.slot_req >= 0
        if not live.any():
            with TraceAnnotation("serve.wait"):
                jumps = [s for s in (
                    lc.next_eligible_step(),
                    source.next_arrival_step(lc, step)
                    if source is not None else None) if s is not None]
                if not jumps:
                    raise RuntimeError(
                        "serve loop stalled: no occupied slots, empty "
                        f"queue, but {lc.open_count()} request(s) not in a "
                        f"terminal state — a request leaked.  Lifecycle "
                        f"table:\n{lc.table()}")
                # every queued request is in retry backoff (or the next
                # trace arrival is in the future): jump the virtual clock
                # to the earliest eligibility instead of spinning
                step = max(step + 1, min(jumps))
            return True
        # -- one ragged decode step (or the chunk's riding results) ---------
        if chunk is not None:
            # the chunked forward already advanced every riding decode
            # slot; newly admitted slots take their first decode step on
            # the next iteration.  A rider the deadline sweep has released
            # since is neither emitted to nor retired.
            nxt, rode, done, bad = chunk
            done = [s for s in done if live[s]]
            bad = [s for s in bad if live[s]]
            advanced = [s for s in rode if live[s] and s not in bad]
        else:
            t0 = time.monotonic()
            try:
                nxt, done, bad = server.decode_step(step)
            except faults.KernelDispatchFault:
                # graceful degradation: finish the step on the jnp
                # reference path and quarantine the tuned decode plan for
                # re-tune
                kernel_fallbacks += 1
                dp = next((p for p in server.kernel_plan
                           if p.op == "attn_decode"), None)
                if dp is not None:
                    autotune.mark_plan_poisoned(dp.plan.key)
                nxt, done, bad = server.decode_step(step, use_ref=True)
            except paging.PageOOM:
                # Pool overcommit mid-decode (reservations disabled, or a
                # resume without them): evict the cheapest-to-redo slot —
                # fewest generated tokens, deterministic tie-break — and
                # retry the step with its pages back in the pool.
                kv_ooms += 1
                victim = min((s for s in range(server.batch)
                              if server.slot_req[s] >= 0),
                             key=lambda s: (int(server.slot_len[s]), s))
                vreq = lc.requests[int(server.slot_req[victim])]
                server.release_slot(victim)
                lc.evict(vreq, step, reason="kv_oom")
                step += 1
                return True
            if watchdog is not None:
                watchdog.observe(step, time.monotonic() - t0)
            advanced = [s for s in range(server.batch)
                        if server.slot_req[s] >= 0 and s not in bad]
        note_kv()                    # decode growth can also set the peak
        # tokens for every slot that advanced this step
        with TraceAnnotation("serve.emit"):
            for slot in advanced:
                emit(lc.requests[int(server.slot_req[slot])],
                     int(nxt[slot, 0]))
                generated += 1
        with TraceAnnotation("serve.retire"):
            for slot in bad:
                # quarantine exactly the poisoned slot: reset + requeue;
                # the neighbours' rows were never touched (per-slot masked
                # writes)
                req = lc.requests[int(server.slot_req[slot])]
                server.release_slot(slot)
                lc.evict(req, step, reason="nan_decode")
            for slot in done:
                req = lc.requests[int(server.slot_req[slot])]
                lc.transition(req, State.COMPLETED, step)
                server.release_slot(slot)
        step += 1
        return True

    # Compiles are counted only while the loop runs: the listener is
    # process-wide.
    jax.monitoring.register_event_duration_secs_listener(count_compile)
    try:
        more = True
        while more and pending():
            with TraceAnnotation("serve.iter", step=step) as it:
                before = emitted
                more = iterate()
                it.set_metadata(occupied=occupied, tokens=emitted - before)
    finally:
        jax.monitoring.unregister_event_duration_listener(count_compile)
    if not lc.conserved():
        raise RuntimeError(
            "request conservation violated after drain: "
            f"{lc.counters()} vs submitted={lc.submitted}.  Lifecycle "
            f"table:\n{lc.table()}")
    return {"generated": generated, "steps": step,
            "kernel_fallbacks": kernel_fallbacks,
            "first_new_token_s": first_new_token_s,
            "max_concurrent": max_concurrent,
            "kv_pages_peak": kv_pages_peak,
            "kv_peak": kv_peak,
            "kv_ooms": kv_ooms,
            "chunked_prefills": chunked_prefills,
            "compiles": compiles,
            "snapshots_saved": 0 if snapshots is None else snapshots.saved}


def serving_chip() -> hardware.Chip:
    """Constants of the chip this process serves on.  On a TPU they are
    looked up by the ``device_kind`` JAX reports, and a kind with no row
    in `hardware.CHIPS` raises; elsewhere (CPU runs) the modelled target,
    `hardware.TPU_V5E`."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return hardware.chip_for(dev.device_kind)
    return hardware.TPU_V5E


def build_fault_plan(*, chaos: bool, fault_seed: int, crash: bool,
                     crash_step: int | None = None):
    """The run's fault schedule: the smoke plan (--chaos), a seeded crash
    (--crash [--crash-step]), or their merge.  None = no injection."""
    plan = faults.FaultPlan.smoke(fault_seed) if chaos else None
    if crash:
        cp = faults.FaultPlan.crash(fault_seed, step=crash_step)
        plan = cp if plan is None else plan.merge(cp)
    return plan


def prepare_resume(state_dir, cfg=None) -> dict:
    """Rebuild the complete serving state of a crashed run from its
    ``--state-dir`` (docs/ROBUSTNESS.md, "Crash recovery").

    Three durable artifacts drive the reconstruction:

    * ``serving.json`` — the static serving context (arch, batch, cache
      geometry, fault schedule, clock rate), written atomically at run
      start so even a crash *before the first snapshot* is resumable;
    * the newest committed snapshot (``snaps/``) — lifecycle table +
      server arrays + injector state at some step S;
    * the journal tail — every record with ``seq`` past the snapshot's
      covered prefix, folded on top to bring the lifecycle to the crash
      point (bounded by the snapshot interval).

    In-flight requests are re-placed onto slots: a slot whose snapshot
    cache already matches the journal (same token count, same last token)
    is kept bitwise; one that advanced past the snapshot — or never made
    it into one — is rebuilt by `Server.restore_slot`'s deterministic
    re-prefill, which *verifies* the journaled continuation.  Requests
    the crash caught mid-transition (PREFILLING, EVICTED, token-less
    DECODING) are demoted to QUEUED and start over, exactly like a fault
    retry.  Must be called inside the mesh/sharding-rules context.

    Returns a dict: cfg, serving, server, lc, journal, snapshots,
    injector, source, step_us, start_step, recovery (the summary block).
    """
    import collections

    sd = pathlib.Path(state_dir)
    serving_path = sd / "serving.json"
    if not serving_path.exists():
        raise FileNotFoundError(
            f"{serving_path}: no serving.json — --resume needs the "
            f"--state-dir of a previous journaled run")
    serving = json.loads(serving_path.read_text())
    if cfg is None:
        cfg = (configs.get_smoke(serving["arch"]) if serving["smoke"]
               else configs.get(serving["arch"]))

    records = journal_mod.read_journal(sd / "journal.jsonl")
    snap = snapshot_mod.latest_snapshot(sd / "snaps")
    step_us = serving.get("step_time_us")
    clock = loadgen.VirtualClock(step_us * 1e-6) if step_us else None

    if snap is not None:
        manifest, arrays = snap
        snap_step = int(manifest["step"])
        start_seq = int(manifest["journal_seq"])
        lc = snapshot_mod.restore_lifecycle(manifest["meta"]["lifecycle"],
                                            clock=clock)
        inj_state = manifest["meta"].get("injector")
    else:
        manifest, arrays = None, None
        snap_step, start_seq = 0, 0
        lc = Lifecycle(queue_limit=serving["queue_limit"],
                       max_retries=serving["max_retries"],
                       **({} if clock is None else {"clock": clock}))
        inj_state = None

    # -- fold the journal tail onto the snapshot ----------------------------
    # Direct field mutation, not transition(): we are replaying a history
    # the state machine already validated, and the admission queue is
    # rebuilt wholesale below (tail records change its membership).
    queued_order = [r.rid for r in lc._queue]

    def queue_drop(rid: int) -> None:
        if rid in queued_order:
            queued_order.remove(rid)

    tail = [r for r in records if r["seq"] >= start_seq]
    last_step = snap_step
    for rec in tail:
        step = int(rec.get("step", -1))
        last_step = max(last_step, step)
        if clock is not None:
            # virtual time is a pure function of the step, so replayed
            # submit/finish stamps land exactly where the live run put them
            clock.on_step(max(step, snap_step))
        kind = rec["kind"]
        if kind == "submit":
            if rec["rid"] in lc.requests:
                continue
            req = Request(rid=rec["rid"],
                          prompt=np.asarray(rec["prompt"], np.int32),
                          gen_len=int(rec["gen_len"]), submit_t=lc.clock(),
                          ttft_deadline_s=rec.get("ttft_deadline_s"),
                          deadline_s=rec.get("deadline_s"))
            lc.requests[req.rid] = req
        elif kind == "state":
            req = lc.requests[rec["rid"]]
            new = State(rec["state"])
            req.retries = int(rec.get("retries", req.retries))
            if new is State.EVICTED:
                lc.evicted_events += 1
            if new is State.QUEUED:
                req.not_before_step = int(rec.get("not_before_step", 0))
                if req.tokens:
                    req.tokens = []       # eviction requeue discards output
                if step >= 0:             # retry requeue, not admission
                    lc.retried_events += 1
                queue_drop(req.rid)
                queued_order.append(req.rid)
            else:
                queue_drop(req.rid)
            if new in TERMINAL and req.finish_t is None:
                req.finish_t = lc.clock()
            req.state = new
            req.history.append((new, step))
        elif kind == "token":
            req = lc.requests[rec["rid"]]
            del req.tokens[int(rec["i"]):]
            req.tokens.append(int(rec["tok"]))
            if req.first_token_t is None:
                req.first_token_t = lc.clock()

    resume_step = last_step + 1

    # -- demote requests the crash caught mid-transition --------------------
    demoted = []

    def demote(req) -> None:
        req.state = State.QUEUED
        req.tokens = []
        req.not_before_step = resume_step
        req.history.append((State.QUEUED, resume_step))
        queue_drop(req.rid)
        queued_order.append(req.rid)
        demoted.append(req.rid)

    for rid in sorted(lc.requests):
        req = lc.requests[rid]
        if req.state in (State.PREFILLING, State.EVICTED) or (
                req.state is State.DECODING and not req.tokens):
            demote(req)

    lc._queue = collections.deque(
        lc.requests[rid] for rid in queued_order
        if lc.requests[rid].state is State.QUEUED)

    if clock is not None:
        clock.on_step(resume_step)
    else:
        # Wall-clock runs: rebase the restored stamps onto this process's
        # monotonic clock so deadlines don't charge the downtime (or a
        # clock discontinuity) to requests that were making progress.
        times = [t for r in lc.requests.values()
                 for t in (r.submit_t, r.first_token_t, r.finish_t)
                 if t is not None]
        if times:
            offset = time.monotonic() - max(times)
            for r in lc.requests.values():
                r.submit_t += offset
                if r.first_token_t is not None:
                    r.first_token_t += offset
                if r.finish_t is not None:
                    r.finish_t += offset

    # -- injector: same seeded schedule, minus the crash that fired ---------
    plan = build_fault_plan(chaos=serving.get("chaos", False),
                            fault_seed=serving.get("fault_seed", 0),
                            crash=serving.get("crash", False),
                            crash_step=serving.get("crash_step"))
    injector = None
    if plan is not None:
        if inj_state is None:
            # crash before the first snapshot: the full plan is pending;
            # prefill ordinals are recovered by counting journaled prefills
            inj_state = {"pending": plan.record(), "fired": [],
                         "prefill_count": sum(
                             1 for r in records if r["kind"] == "state"
                             and r["state"] == State.PREFILLING.value)}
        injector = faults.FaultInjector.restore(plan, inj_state,
                                                resume_step=resume_step)

    # -- server: snapshot arrays + deterministic re-prefill -----------------
    pg = serving.get("paging")
    paged = (paging.PageSpec(page_size=int(pg["page_size"]),
                             num_pages=int(pg["num_pages"]),
                             max_pages=int(pg["max_pages"]))
             if pg else None)
    server = Server(cfg, int(serving["batch"]), int(serving["max_len"]),
                    prefill_len=int(serving["prefill_len"]),
                    slot_lengths=serving["dist"], injector=injector,
                    paged=paged,
                    kv_dtype=jnp.dtype(serving.get("kv_dtype", "float32")))
    if arrays is not None:
        # restore_state re-adopts the page allocator from the restored
        # table (canonical allocation order makes it snapshot-free)
        server.restore_state(arrays)

    reprefilled, placed = [], set()
    for slot in range(server.batch):
        rid = int(server.slot_req[slot])
        if rid < 0:
            continue
        req = lc.requests.get(rid)
        if req is None or req.state is not State.DECODING:
            server.release_slot(slot)     # finished/demoted in the tail
            continue
        if (len(req.tokens) == int(server.slot_len[slot]) + 1
                and int(np.asarray(server.last_tok)[slot, 0])
                == req.tokens[-1]):
            placed.add(rid)               # snapshot already at crash point
            continue
        server.restore_slot(slot, rid, req.prompt, req.tokens, req.gen_len)
        placed.add(rid)
        reprefilled.append(rid)
    for rid in sorted(lc.requests):       # in-flight but not on any slot
        req = lc.requests[rid]
        if req.state is not State.DECODING or rid in placed:
            continue
        free = [s for s in range(server.batch)
                if int(server.slot_req[s]) < 0]
        if not free:
            demote(req)
            lc._queue.append(req)
            continue
        server.restore_slot(free[0], rid, req.prompt, req.tokens,
                            req.gen_len)
        placed.add(rid)
        reprefilled.append(rid)

    # -- scheduler: re-pledge in-flight footprints ---------------------------
    sched_policy = serving.get("sched", "fcfs")
    scheduler = (Scheduler(sched_policy, allocator=server.allocator)
                 if (paged is not None or sched_policy != "fcfs") else None)
    if server.allocator is not None:
        # The dead process's reservations died with it; re-pledge each
        # placed request's *remaining* footprint so post-resume admission
        # prices the pool exactly like the uninterrupted run.
        for slot in range(server.batch):
            rid = int(server.slot_req[slot])
            if rid < 0 or rid not in lc.requests:
                continue
            req = lc.requests[rid]
            total = int(len(req.prompt)) + int(req.gen_len)
            short = (server.allocator.pages_for(total)
                     - server.allocator.slot_pages(slot))
            if short > 0:
                server.allocator.reserve(rid, short * paged.page_size)

    # -- arrival source: re-cursor past the journaled prefix ----------------
    source = None
    if serving.get("load_trace"):
        trace = loadgen.load_trace(serving["load_trace"])
        source = loadgen.TraceSource(trace, cfg.vocab_size)
        source.skip_submitted(lc)

    # -- reattach durability (Journal.__init__ truncates a torn tail) -------
    journal = journal_mod.Journal(sd / "journal.jsonl")
    lc.journal = journal
    snapshots = snapshot_mod.SnapshotStore(
        sd / "snaps", every=serving.get("snapshot_every", 8),
        keep=serving.get("snapshot_keep", 3))

    recovery = {
        "resumed": True,
        "snapshot_step": None if manifest is None else snap_step,
        "resume_step": resume_step,
        "replayed_steps": resume_step - snap_step,
        "replayed_records": len(tail),
        "reprefilled_slots": len(reprefilled),
        "restored_requests": len(lc.requests),
        "demoted": demoted,
    }
    return {"cfg": cfg, "serving": serving, "server": server, "lc": lc,
            "journal": journal, "snapshots": snapshots,
            "injector": injector, "source": source, "step_us": step_us,
            "start_step": resume_step, "recovery": recovery,
            "scheduler": scheduler}


def _summary(server, lc, stats, wall, *, batch, batch_source,
             watchdog, scheduler=None) -> dict:
    """The final conservation-bearing summary line (shared between a
    fresh run and `serve --resume`)."""
    outcomes = lc.counters()
    out = {
        "arch": server.cfg.name,
        "requests": outcomes["completed"],      # back-compat: served count
        "submitted": lc.submitted,
        "batch": batch, "batch_source": batch_source,
        "tokens_generated": stats["generated"],
        "decode_steps": stats["steps"],
        "wall_s": round(wall, 2),
        "tok_per_s": round(stats["generated"] / max(wall, 1e-9), 1),
        "outcomes": outcomes,
        "retries_total": lc.retried_events,
        "kernel_fallbacks": stats["kernel_fallbacks"],
        "snapshots_saved": stats.get("snapshots_saved", 0),
        "max_concurrent": stats.get("max_concurrent", 0),
        "chunked_prefills": stats.get("chunked_prefills", 0),
        # programs lowered while serving: each new prompt or chunk width
        # compiles inside the loop
        "compiles": stats["compiles"],
        # blocking device-to-host reads: one per prefill, chunk and decode
        # step (paged mode adds one per decode step and chunk)
        "host_reads": server.host_reads,
        "ttft_ms": lc.ttft_percentiles(),
        "per_token_ms": lc.per_token_percentiles(),
        "request_outcomes": lc.outcome_trace(),
        "watchdog": watchdog.summary(),
        "kv_dtype": server.kv_dtype.name,
        "param_bytes": server.param_bytes,
        "kernel_plan": [p.record() for p in server.kernel_plan],
    }
    if scheduler is not None:
        out["sched"] = {"policy": scheduler.policy,
                        "rejected_oversize": scheduler.rejected_oversize}
    if server.allocator is not None:
        # KV-memory utilization: pages allocated vs tokens actually
        # resident in them at drain (plus the run's peak), the numbers
        # BENCH_serving.json's paging comparison is gated on.
        resident = int(np.asarray(server.cache["lengths"])[
            server.slot_req >= 0].sum())
        out["kv"] = {**server.allocator.utilization(resident),
                     "pages_peak": stats.get("kv_pages_peak", 0),
                     "peak": stats.get("kv_peak"),
                     "kv_ooms": stats.get("kv_ooms", 0)}
    return out


def _run_resume(args) -> int:
    """`serve --resume`: rebuild from --state-dir and drain to a summary
    whose completions are token-for-token those of the uninterrupted
    run."""
    mesh = make_host_mesh(data=1, model=1)
    rules = specs.rules_for(mesh)
    t0 = time.time()
    try:
        with jax.set_mesh(mesh), shd.use_rules(rules):
            R = prepare_resume(args.state_dir)
            server, lc, serving = R["server"], R["lc"], R["serving"]
            if R["injector"] is not None:
                autotune.install_dispatch_hook(R["injector"].dispatch_hook)
            predicted_us = (autotune.predict_decode_step_us(
                server.cfg, server.batch, cache_len=server.max_len,
                kv_dtype=server.kv_dtype,
                lengths=autotune._quantile_lengths(
                    server.batch, serving["dist"], server.max_len),
                plans=server.kernel_plan, chip=serving_chip())
                if server.kernel_plan else None)
            watchdog = fault_tolerance.DecodeWatchdog(predicted_us)
            prep_s = time.time() - t0
            print(json.dumps({"recovery": {**R["recovery"],
                                           "prepare_s": round(prep_s, 3)}}))
            try:
                stats = serve_loop(server, lc, watchdog=watchdog,
                                   source=R["source"], journal=R["journal"],
                                   snapshots=R["snapshots"],
                                   start_step=R["start_step"],
                                   scheduler=R["scheduler"])
            except faults.CrashFault as cf:
                print(json.dumps({"crash": {"step": cf.step,
                                            "msg": str(cf),
                                            "state_dir": args.state_dir}}))
                R["journal"].close()
                return CRASH_EXIT
            wall = time.time() - t0
            R["journal"].close()
    finally:
        autotune.install_dispatch_hook(None)

    summary = _summary(server, lc, stats, wall, batch=server.batch,
                       batch_source="resume", watchdog=watchdog,
                       scheduler=R["scheduler"])
    summary["recovery"] = {
        **R["recovery"],
        "prepare_s": round(prep_s, 3),
        # --resume start -> first newly generated token: the recovery-
        # latency number the serving benchmark's `recovery` row reports
        "first_new_token_s": (
            None if stats["first_new_token_s"] is None
            else round(prep_s + stats["first_new_token_s"], 3)),
    }
    if R["injector"] is not None:
        summary["faults"] = R["injector"].record()
    if R["source"] is not None:
        summary["load"] = {
            "trace": serving.get("load_trace"),
            "arrivals": len(R["source"].trace),
            "step_time_us": (None if R["step_us"] is None
                             else round(R["step_us"], 3)),
            "queue_depth_max": max((q[1] for q in R["source"].queue_depth),
                                   default=0),
        }
    print(json.dumps(summary))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_14b",
                    choices=configs.list_archs() + configs.list_cuts())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=0,
                    help="decode batch; 0 = let the autotuner pick "
                         "(select_serving_batch sweep)")
    ap.add_argument("--batch-candidates", type=int, nargs="+",
                    default=[1, 2, 4, 8, 16, 32])
    ap.add_argument("--latency-budget-ms", type=float, default=None,
                    help="per-decode-step latency ceiling for the batch "
                         "sweep (None = pure throughput)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: a fixed pool of page-size-token "
                         "KV blocks shared across slots through per-slot "
                         "page tables (docs/PAGING.md)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (with --paged)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="physical pages in the shared pool; 0 = "
                         "contiguous-equivalent "
                         "(batch * ceil(max_len / page_size))")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="KV-cache storage dtype: int8 streams quantized "
                         "K/V + per-row scales through the decode_int8 "
                         "kernel family (~1.9x fewer bytes per token at "
                         "dh=64)")
    ap.add_argument("--sched", default="fcfs", choices=list(POLICIES),
                    help="admission policy over the request queue; with "
                         "--paged admission is additionally gated on the "
                         "allocator covering the request's predicted "
                         "KV footprint")
    ap.add_argument("--queue-limit", type=int, default=0,
                    help="admission-queue bound; submits past it are "
                         "REJECTED (0 = unbounded)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="retry budget for evicted/faulted requests")
    ap.add_argument("--ttft-ms", type=float, default=None,
                    help="time-to-first-token deadline per request")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="total deadline per request")
    ap.add_argument("--chaos", action="store_true",
                    help="inject the deterministic smoke fault schedule "
                         "(one fault of each class)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the --chaos fault schedule")
    ap.add_argument("--load-trace", default=None,
                    help="replay a runtime.loadgen JSONL trace: arrivals "
                         "fire on a deterministic virtual clock (one "
                         "predicted decode-step per loop step) instead of "
                         "submitting --requests synthetic prompts at t0")
    ap.add_argument("--step-time-us", type=float, default=0.0,
                    help="virtual decode-step time for --load-trace "
                         "replay; 0 = the tuner's predicted step time")
    ap.add_argument("--state-dir", default=None,
                    help="directory for the request journal + state "
                         "snapshots (enables crash tolerance and "
                         "--resume)")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="decode steps between state snapshots")
    ap.add_argument("--snapshot-keep", type=int, default=3,
                    help="committed snapshots retained after pruning")
    ap.add_argument("--crash", action="store_true",
                    help="inject a seeded crash fault: the process dies "
                         f"mid-serve (exit {CRASH_EXIT}) leaving only "
                         "the journal + snapshots; combine with "
                         "--state-dir, then `serve --resume`")
    ap.add_argument("--crash-step", type=int, default=None,
                    help="pin the --crash decode step (default: seeded)")
    ap.add_argument("--resume", action="store_true",
                    help="resume a crashed run from --state-dir instead "
                         "of starting fresh")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.resume:
        if not args.state_dir:
            ap.error("--resume requires --state-dir")
        return _run_resume(args)

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    if cfg.family == "encoder":
        print("encoder-only arch has no decode path; nothing to serve")
        return 0
    kv_dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                "int8": jnp.int8}[args.kv_dtype]
    chip = serving_chip()
    mesh = make_host_mesh(data=1, model=1)
    rules = specs.rules_for(mesh)

    trace = None
    if args.load_trace:
        # Replay mode: the workload comes from the trace file, so the
        # slot-depth distribution and cache allocation are derived from
        # its actual lengths (midpoint depth per request = a slot serving
        # it spends its steady state there).
        trace = loadgen.load_trace(args.load_trace)
        args.requests = len(trace)
        prefill_len = max(t.prompt_len for t in trace)
        max_len = max(t.prompt_len + t.gen_len for t in trace) + 8
        dist = sorted(t.prompt_len + t.gen_len // 2 for t in trace)
    else:
        prefill_len = args.prompt_len
        max_len = args.prompt_len + args.gen + 8
        # Steady-state slot-depth distribution: continuous batching
        # staggers occupied slots roughly uniformly across
        # [prompt, prompt + gen] — the length model the batch sweep and
        # the decode-plan tuning both price.
        n_dist = max(args.batch_candidates + [args.batch, 1])
        dist = [args.prompt_len + ((2 * i + 1) * args.gen) // (2 * n_dist)
                for i in range(n_dist)]

    if args.batch > 0:
        batch = args.batch
        decision = {"batch": batch, "source": "flag"}
    else:
        # The tuner drives the batch: predicted-throughput argmax under the
        # latency budget, from the same cached plans the kernels run with.
        # Candidates beyond the queued workload are pointless (empty slots
        # still pay the step), so cap the sweep at --requests.
        cands = [c for c in args.batch_candidates if c <= args.requests]
        cands = cands or [min(args.batch_candidates)]
        # The sweep prices each candidate at quantiles of the slot-depth
        # distribution — the ragged batch the kernel actually skips on,
        # not the batch-max broadcast that over-charges every short slot.
        decision = autotune.select_serving_batch(
            cfg, cache_len=max_len, prefill_len=prefill_len,
            kv_dtype=kv_dtype,             # the Server's cache dtype
            candidates=tuple(cands),
            slot_lengths=dist,
            latency_budget_ms=args.latency_budget_ms,
            pool_pages=(args.pool_pages or None) if args.paged else None,
            page_size=args.page_size if args.paged else None, chip=chip)
        decision["source"] = "autotune"
        batch = decision["batch"]
    print(json.dumps({"serving_plan": decision}))

    paged = None
    if args.paged:
        if cfg.family not in ("dense", "moe") or not cfg.causal \
                or cfg.sliding_window:
            ap.error("--paged needs a dense/moe causal arch without "
                     "sliding-window attention (the SWA ring buffer is "
                     "contiguous-only)")
        paged = paging.PageSpec.build(batch, max_len, args.page_size,
                                      pool_pages=args.pool_pages)
        print(json.dumps({"paging": {"page_size": paged.page_size,
                                     "num_pages": paged.num_pages,
                                     "max_pages": paged.max_pages}}))

    injector = None
    plan = build_fault_plan(chaos=args.chaos, fault_seed=args.fault_seed,
                            crash=args.crash, crash_step=args.crash_step)
    if plan is not None:
        injector = faults.FaultInjector(plan)
        autotune.install_dispatch_hook(injector.dispatch_hook)
        print(json.dumps({"fault_plan": {"seed": args.fault_seed,
                                         "schedule": plan.record()}}))

    journal = None
    snapshots = None
    state_dir = pathlib.Path(args.state_dir) if args.state_dir else None
    if state_dir is not None:
        # A fresh run owns its state dir: stale journal/snapshot artifacts
        # from a previous run would corrupt recovery accounting.
        state_dir.mkdir(parents=True, exist_ok=True)
        (state_dir / "journal.jsonl").unlink(missing_ok=True)
        for p in (state_dir / "snaps").glob("snap-*"):
            p.unlink()
        journal = journal_mod.Journal(state_dir / "journal.jsonl")
        snapshots = snapshot_mod.SnapshotStore(state_dir / "snaps",
                                               every=args.snapshot_every,
                                               keep=args.snapshot_keep)

    source = None
    step_us = None
    if trace is not None:
        # Virtual clock: one predicted decode-step of wall time per loop
        # step, so TTFT / per-token percentiles are deterministic and
        # denominated in model-milliseconds.
        step_us = args.step_time_us or loadgen.virtual_step_us(
            decision.get("predicted_step_us")
            or autotune.predict_decode_step_us(
                cfg, batch, cache_len=max_len, kv_dtype=kv_dtype,
                lengths=autotune._quantile_lengths(batch, dist, max_len),
                chip=chip))
        clock = loadgen.VirtualClock(step_us * 1e-6)
        source = loadgen.TraceSource(trace, cfg.vocab_size)
        lc = Lifecycle(queue_limit=args.queue_limit,
                       max_retries=args.max_retries, clock=clock,
                       journal=journal)
    else:
        rng = np.random.default_rng(0)
        reqs = [(i, rng.integers(0, cfg.vocab_size, size=args.prompt_len),
                 args.gen) for i in range(args.requests)]
        lc = Lifecycle(queue_limit=args.queue_limit,
                       max_retries=args.max_retries, journal=journal)
        for rid, prompt, gen in reqs:
            lc.submit(rid, prompt, gen,
                      ttft_deadline_s=(args.ttft_ms / 1e3
                                       if args.ttft_ms else None),
                      deadline_s=(args.deadline_ms / 1e3
                                  if args.deadline_ms else None))

    if state_dir is not None:
        # The static serving context, durable before any decode step can
        # crash: `serve --resume` derives the server geometry, clock rate
        # and fault schedule from this even when the crash predates the
        # first snapshot.
        snapshot_mod.atomic_write_json(state_dir / "serving.json", {
            "arch": args.arch, "smoke": bool(args.smoke),
            "batch": batch, "max_len": max_len,
            "prefill_len": prefill_len, "dist": [int(d) for d in dist],
            "decision": decision,
            "queue_limit": args.queue_limit,
            "max_retries": args.max_retries,
            "snapshot_every": args.snapshot_every,
            "snapshot_keep": args.snapshot_keep,
            "step_time_us": step_us,
            "load_trace": args.load_trace,
            "chaos": bool(args.chaos), "fault_seed": args.fault_seed,
            "crash": bool(args.crash), "crash_step": args.crash_step,
            "requests": args.requests, "prompt_len": args.prompt_len,
            "gen": args.gen,
            "ttft_ms": args.ttft_ms, "deadline_ms": args.deadline_ms,
            "paging": (None if paged is None else
                       {"page_size": paged.page_size,
                        "num_pages": paged.num_pages,
                        "max_pages": paged.max_pages}),
            "sched": args.sched,
            "kv_dtype": jnp.dtype(kv_dtype).name,
        })

    try:
        with jax.set_mesh(mesh), shd.use_rules(rules):
            server = Server(cfg, batch, max_len,
                            prefill_len=prefill_len,
                            slot_lengths=dist, injector=injector,
                            paged=paged, kv_dtype=kv_dtype)
            scheduler = (Scheduler(args.sched, allocator=server.allocator)
                         if (paged is not None or args.sched != "fcfs")
                         else None)
            predicted_us = (autotune.predict_decode_step_us(
                cfg, batch, cache_len=max_len, kv_dtype=kv_dtype,
                lengths=autotune._quantile_lengths(batch, dist, max_len),
                plans=server.kernel_plan, chip=chip)
                if server.kernel_plan else None)
            watchdog = fault_tolerance.DecodeWatchdog(predicted_us)
            t0 = time.time()
            try:
                stats = serve_loop(server, lc, watchdog=watchdog,
                                   source=source, journal=journal,
                                   snapshots=snapshots,
                                   scheduler=scheduler)
            except faults.CrashFault as cf:
                # The one fault class the process must NOT absorb: die
                # with no summary (the conservation line never prints) and
                # a distinct exit code.  Only the journal + snapshots
                # survive, for `serve --resume`.
                print(json.dumps({"crash": {"step": cf.step,
                                            "msg": str(cf),
                                            "state_dir": args.state_dir}}))
                if journal is not None:
                    journal.close()
                return CRASH_EXIT
            wall = time.time() - t0
            if journal is not None:
                journal.close()
    finally:
        autotune.install_dispatch_hook(None)

    summary = _summary(server, lc, stats, wall, batch=batch,
                       batch_source=decision["source"], watchdog=watchdog,
                       scheduler=scheduler)
    if injector is not None:
        summary["faults"] = injector.record()
    if source is not None:
        summary["load"] = {
            "trace": args.load_trace,
            "arrivals": len(trace),
            "step_time_us": round(step_us, 3),
            "queue_depth_max": max((q[1] for q in source.queue_depth),
                                   default=0),
        }
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
