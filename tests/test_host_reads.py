"""The serve engine reads each call's results from the device once: every
`Server.prefill`, `admit_chunk` and `decode_step` makes one blocking
device-to-host read (its ``.sync``), of the tokens and the guard in one
array, and returns host tokens; the step itself advances ``last_tok`` on
the device.  Counted by `Server.host_reads` (paged mode adds its read of
the cache depths before each decode step and chunk)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.serve import Server, serve_loop
from repro.models.config import ModelConfig
from repro.runtime import faults
from repro.runtime.lifecycle import Lifecycle, State
from repro.runtime.paging import PageSpec

MAX_LEN = 32


def _cfg():
    return ModelConfig(name="tiny-reads", family="dense", num_layers=2,
                       d_model=32, d_ff=64, vocab_size=101, num_heads=4,
                       num_kv_heads=2)


def _prompt(rid, n):
    return np.random.default_rng(rid).integers(0, 101, n, dtype=np.int32)


class Scripted:
    """Submits each group of ``(rid, prompt_len, gen_len)`` at its step."""

    def __init__(self, groups):
        self.groups = sorted(groups.items())
        self.i = 0

    def pump(self, lc, step):
        while self.i < len(self.groups) and self.groups[self.i][0] <= step:
            for rid, plen, gen in self.groups[self.i][1]:
                lc.submit(rid, _prompt(rid, plen), gen)
            self.i += 1

    def exhausted(self):
        return self.i >= len(self.groups)

    def next_arrival_step(self, lc, step):
        return None if self.exhausted() else self.groups[self.i][0]


class Calls:
    """Counts the server's calls that returned, and checks what each
    returned: host tokens, and a device ``last_tok`` that holds each
    advanced slot's emitted token and keeps a bad slot's old one."""

    def __init__(self, server):
        self.server = server
        self.n = {"prefill": 0, "chunk": 0, "decode": 0}
        prefill, chunk, decode = (server.prefill, server.admit_chunk,
                                  server.decode_step)

        def last():
            return np.asarray(server.last_tok)[:, 0].copy()

        def on_prefill(slot, *a, **kw):
            ok, first = prefill(slot, *a, **kw)
            assert isinstance(ok, bool) and type(first) is int
            assert last()[slot] == first
            self.n["prefill"] += 1
            return ok, first

        def on_chunk(admits, *a, **kw):
            before = last()
            ok_admit, nxt, rode, done, bad = chunk(admits, *a, **kw)
            self._host_tokens(nxt)
            took = {slot for slot, *_ in admits} | (set(rode) - set(bad))
            self._advanced(before, last(), nxt, took)
            self.n["chunk"] += 1
            return ok_admit, nxt, rode, done, bad

        def on_decode(*a, **kw):
            before = last()
            live = {s for s in range(server.batch)
                    if server.slot_req[s] >= 0}
            nxt, done, bad = decode(*a, **kw)
            self._host_tokens(nxt)
            self._advanced(before, last(), nxt, live - set(bad))
            self.n["decode"] += 1
            return nxt, done, bad

        server.prefill = on_prefill
        server.admit_chunk = on_chunk
        server.decode_step = on_decode

    def _host_tokens(self, nxt):
        assert isinstance(nxt, np.ndarray)
        assert nxt.shape == (self.server.batch, 1) and nxt.dtype == np.int32

    @staticmethod
    def _advanced(before, after, nxt, took):
        for s in range(len(before)):
            want = nxt[s, 0] if s in took else before[s]
            assert after[s] == want, (s, before, after, nxt)

    def reads(self, paged: bool) -> int:
        """The reads the calls should have made."""
        per_call = sum(self.n.values())
        return per_call + (self.n["decode"] + self.n["chunk"] if paged
                           else 0)


# one request decoding, two arriving at once beside it (a chunk with a
# rider), then a lone arrival after the loop went idle (a one-slot
# prefill)
SCENARIO = {0: [(0, 5, 9)], 2: [(1, 4, 5), (2, 7, 6)], 30: [(3, 6, 4)]}
SERVERS = {
    "f32": {},
    "int8": {"kv_dtype": jnp.int8},
    "paged": {"paged": PageSpec.build(3, MAX_LEN, page_size=4)},
}


def _serve(kind, batch, scenario):
    server = Server(_cfg(), batch, MAX_LEN, autotune_kernels=False,
                    **SERVERS[kind])
    calls = Calls(server)
    lc = Lifecycle()
    stats = serve_loop(server, lc, source=Scripted(scenario))
    return server, calls, lc, stats


@pytest.mark.parametrize("kind", sorted(SERVERS))
def test_one_read_per_call(kind):
    server, calls, lc, stats = _serve(kind, 3, SCENARIO)
    assert calls.n["chunk"] == stats["chunked_prefills"] == 1
    assert calls.n["prefill"] == 2 and calls.n["decode"] > 0
    assert server.host_reads == calls.reads(kind == "paged")
    assert all(r.state is State.COMPLETED for r in lc.requests.values())
    # the batched run serves what each request served alone does
    for rid, req in lc.requests.items():
        _, _, solo, _ = _serve(kind, 1, {0: [(rid, req.prompt.size,
                                              req.gen_len)]})
        assert req.tokens == solo.requests[rid].tokens, rid


def _chaos(plan):
    injector = (faults.FaultInjector(plan, sleep=lambda s: None)
                if plan is not None else None)
    server = Server(_cfg(), 2, MAX_LEN, autotune_kernels=False,
                    injector=injector)
    calls = Calls(server)
    lc = Lifecycle(max_retries=2, clock=lambda: 0.0)
    for rid, (plen, gen) in enumerate([(5, 8), (7, 8)]):
        lc.submit(rid, _prompt(rid, plen), gen)
    stats = serve_loop(server, lc)
    return server, calls, lc, stats


def test_poisoned_slot_is_quarantined_alone_with_one_read_per_call():
    """NaN logits on slot 0 at step 3 and a kernel-dispatch fault at step
    5: the poisoned slot alone is evicted in the step it went bad (its
    ``last_tok`` kept), the fallback step reads once, and every request's
    tokens equal the fault-free run's."""
    _, _, base, _ = _chaos(None)
    plan = faults.FaultPlan([faults.FaultEvent("nan_logits", 3, 0),
                             faults.FaultEvent("kernel_dispatch", 5, 0)])
    server, calls, lc, stats = _chaos(plan)
    assert stats["kernel_fallbacks"] == 1
    assert lc.counters()["evicted"] == 1
    (hit,) = [r for r in lc.requests.values() if r.retries == 1]
    assert (State.EVICTED, 3) in hit.history
    assert server.host_reads == calls.reads(paged=False)
    assert {r: q.tokens for r, q in lc.requests.items()} == {
        r: q.tokens for r, q in base.requests.items()}
