"""Fault injection end-to-end: the serve loop under the chaos harness.

The invariants: (1) a seeded fault schedule replays bit-for-bit — same
``--fault-seed``, same outcome trace; (2) faults are *absorbed*, not
propagated — a NaN-poisoned slot is quarantined alone while its
neighbours' tokens stay bitwise identical to a fault-free run, an
evicted-then-retried request reproduces solo decode token-for-token
(slot recycling is exact), and a kernel-dispatch failure completes the
step on the jnp reference path with identical tokens; (3) the drain loop
conserves every request and fails loudly (lifecycle table) instead of
spinning when progress is impossible."""

import numpy as np
import pytest

import jax

from repro.launch.serve import Server, serve_loop
from repro.models.config import ModelConfig
from repro.runtime import fault_tolerance, faults
from repro.runtime.lifecycle import Lifecycle, State, submit_all

MAX_LEN = 24


def _cfg(**kw):
    base = dict(name="tiny-chaos", family="dense", num_layers=2, d_model=32,
                d_ff=64, vocab_size=101, num_heads=4, num_kv_heads=2)
    base.update(kw)
    return ModelConfig(**base)


def _requests(cfg, spec):
    """spec: list of (prompt_len, gen_len) -> [(rid, prompt, gen)]."""
    out = []
    for rid, (plen, gen) in enumerate(spec):
        prompt = np.asarray(
            jax.random.randint(jax.random.PRNGKey(100 + rid), (plen,), 0,
                               cfg.vocab_size), np.int32)
        out.append((rid, prompt, gen))
    return out


def _run(cfg, batch, reqs, *, plan=None, max_retries=2, max_len=MAX_LEN):
    injector = (faults.FaultInjector(plan, sleep=lambda s: None)
                if plan is not None else None)
    server = Server(cfg, batch, max_len, autotune_kernels=False,
                    injector=injector)
    lc = Lifecycle(max_retries=max_retries, clock=lambda: 0.0)
    submit_all(lc, reqs)
    stats = serve_loop(server, lc)
    return lc, stats, injector


def _tokens(lc):
    return {rid: list(lc.requests[rid].tokens) for rid in lc.requests}


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

def test_fault_plan_is_seed_deterministic():
    p1 = faults.FaultPlan.smoke(7)
    p2 = faults.FaultPlan.smoke(7)
    assert p1.record() == p2.record()
    assert {e.kind for e in p1.events} == set(faults.SMOKE_FAULT_CLASSES)
    assert faults.FaultPlan.smoke(8).record() != p1.record()


def test_same_fault_seed_identical_outcome_trace():
    """The chaos acceptance invariant: the full smoke schedule replayed
    under the same seed produces the same per-request final states, retry
    counts, fired-fault records, and generated tokens."""
    cfg = _cfg()
    spec = [(5, 10), (4, 10), (6, 10), (3, 10), (5, 10), (4, 10)]
    runs = []
    for _ in range(2):
        lc, stats, injector = _run(cfg, 2, _requests(cfg, spec),
                                   plan=faults.FaultPlan.smoke(3))
        # first_new_token_s is wall-clock (volatile by contract, like
        # loadgen's VOLATILE_FIELDS) and compiles depends on what the
        # process compiled before — everything else must replay exactly
        stats = {k: v for k, v in stats.items()
                 if k not in ("first_new_token_s", "compiles")}
        runs.append((lc.outcome_trace(), injector.record(), _tokens(lc),
                     stats))
    assert runs[0] == runs[1]
    trace = runs[0][0]
    assert all(row["state"] in ("completed", "failed") for row in trace)
    # the schedule actually exercised the machinery somewhere
    assert sum(row["retries"] for row in trace) >= 1


# ---------------------------------------------------------------------------
# absorption: quarantine, retry-reproduces-solo, kernel fallback
# ---------------------------------------------------------------------------

def test_nan_quarantine_isolates_the_poisoned_slot():
    """A NaN-logits fault evicts exactly one slot; the neighbour's tokens
    are bitwise identical to the fault-free run, and the retried request —
    restarted from a zeroed slot — reproduces its fault-free tokens too."""
    cfg = _cfg()
    spec = [(5, 8), (7, 8)]                  # requests == batch: no refills
    reqs = _requests(cfg, spec)
    base, _, _ = _run(cfg, 2, reqs)
    plan = faults.FaultPlan([faults.FaultEvent("nan_logits", 3, 0)])
    lc, _, injector = _run(cfg, 2, reqs, plan=plan)
    assert not lc._queue and lc.conserved()
    fired = injector.record()["fired"]
    assert len(fired) == 1 and not fired[0].get("skipped")
    hit_rid = next(r for r in lc.requests.values() if r.retries == 1).rid
    assert lc.counters() == {"completed": 2, "timed_out": 0, "failed": 0,
                             "rejected": 0, "evicted": 1, "retried": 1}
    for rid, prompt, gen in reqs:
        assert _tokens(lc)[rid] == _tokens(base)[rid], (
            f"request {rid} ({'poisoned' if rid == hit_rid else 'neighbour'})"
            f" diverged from the fault-free run")
        assert len(_tokens(lc)[rid]) == gen + 1


def test_kv_corruption_evicted_then_retried_matches_solo():
    """Poisoned *state* (NaN over a slot's KV rows): the guard catches the
    slot on its next step, and the retry — through slot recycling — matches
    the request served alone, token for token."""
    cfg = _cfg()
    spec = [(5, 7), (9, 6), (3, 8)]
    reqs = _requests(cfg, spec)
    plan = faults.FaultPlan([faults.FaultEvent("kv_corrupt", 2, 1)])
    lc, _, _ = _run(cfg, 2, reqs, plan=plan)
    assert lc.counters()["evicted"] == 1 and lc.counters()["completed"] == 3
    retried = next(r for r in lc.requests.values() if r.retries == 1)
    for rid, prompt, gen in reqs:
        solo, _, _ = _run(cfg, 1, [(rid, prompt, gen)])
        assert _tokens(lc)[rid] == _tokens(solo)[rid], (
            f"request {rid} (retried={rid == retried.rid}) diverged "
            f"from solo decode")


def test_evicted_then_retried_matches_solo_fused_kernel(monkeypatch,
                                                        tmp_path):
    """The same retry-reproduces-solo invariant with the decode hot loop
    routed through the fused decode-attention kernel (interpret mode)."""
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "interpret")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    cfg = _cfg()
    spec = [(5, 6), (4, 5)]
    reqs = _requests(cfg, spec)
    plan = faults.FaultPlan([faults.FaultEvent("nan_logits", 2, 0)])
    lc, _, _ = _run(cfg, 2, reqs, plan=plan, max_len=16)
    assert lc.counters()["completed"] == 2
    assert any(r.retries == 1 for r in lc.requests.values())
    for rid, prompt, gen in reqs:
        solo, _, _ = _run(cfg, 1, [(rid, prompt, gen)], max_len=16)
        assert _tokens(lc)[rid] == _tokens(solo)[rid]


def test_kernel_dispatch_fault_falls_back_with_identical_tokens():
    """A kernel-dispatch failure mid-run: the step completes on the jnp
    reference path (no eviction, no retries) and every token matches the
    fault-free run — degradation changes speed, never results."""
    cfg = _cfg()
    spec = [(5, 8), (7, 8)]
    reqs = _requests(cfg, spec)
    base, base_stats, _ = _run(cfg, 2, reqs)
    plan = faults.FaultPlan([faults.FaultEvent("kernel_dispatch", 4, 0)])
    lc, stats, _ = _run(cfg, 2, reqs, plan=plan)
    assert stats["kernel_fallbacks"] == 1
    assert base_stats["kernel_fallbacks"] == 0
    assert lc.counters()["evicted"] == 0
    assert all(r.retries == 0 for r in lc.requests.values())
    assert _tokens(lc) == _tokens(base)


def test_prefill_interrupt_evicts_and_retry_completes():
    """An interrupt between slot reset and cache write: the slot is left
    zeroed, the request is evicted + requeued with backoff, and the retry
    reproduces the fault-free tokens."""
    cfg = _cfg()
    reqs = _requests(cfg, [(6, 5)])
    base, _, _ = _run(cfg, 1, reqs)
    plan = faults.FaultPlan([
        faults.FaultEvent("prefill_interrupt", 0, 0)])   # the 1st prefill
    lc, _, injector = _run(cfg, 1, reqs, plan=plan)
    req = lc.requests[0]
    assert req.retries == 1 and req.state is State.COMPLETED
    assert injector.record()["fired"][0]["kind"] == "prefill_interrupt"
    assert _tokens(lc)[0] == _tokens(base)[0]


def test_fault_with_no_retry_budget_fails_cleanly():
    """max_retries=0: the faulted request ends FAILED (not lost, not
    spinning) and the neighbour still completes."""
    cfg = _cfg()
    spec = [(5, 8), (7, 8)]
    reqs = _requests(cfg, spec)
    plan = faults.FaultPlan([faults.FaultEvent("kv_corrupt", 3, 0)])
    lc, _, _ = _run(cfg, 2, reqs, plan=plan, max_retries=0)
    c = lc.counters()
    assert c["completed"] == 1 and c["failed"] == 1 and c["retried"] == 0
    assert lc.conserved()


# ---------------------------------------------------------------------------
# no-progress guard + watchdog
# ---------------------------------------------------------------------------

def test_stalled_loop_fails_loudly_with_lifecycle_table():
    """A leaked request (non-terminal, not queued, not in a slot) must
    raise with the lifecycle table, not spin forever."""
    cfg = _cfg()
    server = Server(cfg, 1, MAX_LEN, autotune_kernels=False)
    lc = Lifecycle(clock=lambda: 0.0)
    submit_all(lc, _requests(cfg, [(4, 3)]))
    leaked = lc.pop_ready(0)                 # popped but never slotted
    lc.transition(leaked, State.PREFILLING, 0)
    with pytest.raises(RuntimeError, match="request leaked") as exc:
        serve_loop(server, lc)
    assert "prefilling" in str(exc.value)    # the table names the state


def test_undrainable_queue_hits_the_step_ceiling():
    cfg = _cfg()
    server = Server(cfg, 1, MAX_LEN, autotune_kernels=False)
    lc = Lifecycle(clock=lambda: 0.0)
    submit_all(lc, _requests(cfg, [(4, 500)]))   # can't finish in 3 steps
    with pytest.raises(RuntimeError, match="without draining"):
        serve_loop(server, lc, max_steps=3)


def test_backoff_only_queue_jumps_virtual_clock_instead_of_spinning():
    """All queued requests in retry backoff + empty batch: the loop must
    jump to the next eligibility step, so total steps stay near the
    backoff horizon instead of ballooning."""
    cfg = _cfg()
    reqs = _requests(cfg, [(6, 5)])
    plan = faults.FaultPlan([faults.FaultEvent("kv_corrupt", 1, 0)])
    lc, stats, _ = _run(cfg, 1, reqs, plan=plan)
    req = lc.requests[0]
    assert req.retries == 1 and req.state is State.COMPLETED
    # eviction at ~step 1, backoff 4 steps, retry decode of 5 tokens:
    # a spinning loop would show no bound; the jump keeps it tight
    assert stats["steps"] <= 20


def test_decode_watchdog_flags_straggler_and_divergence():
    wd = fault_tolerance.DecodeWatchdog(predicted_us=100.0)
    for step in range(10):
        assert wd.observe(step, 100e-6) is None
    report = wd.observe(10, 250e-6)          # 2.5x the rolling median
    assert report is not None and report.ratio == pytest.approx(2.5)
    s = wd.summary()
    assert s["predicted_step_us"] == 100.0
    assert s["measured_step_us_p50"] == pytest.approx(100.0)
    assert s["divergence"] == pytest.approx(1.0)
    assert len(s["stragglers"]) == 1 and s["stragglers"][0]["step"] == 10
