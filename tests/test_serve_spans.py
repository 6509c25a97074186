"""The serve engine's own spans and counters: `serve_loop` and `Server`
annotate their parts as ``serve.<part>`` on the profiler's clock, count
the programs lowered while the loop runs, and behave the same with the
profiler on and off; the benchmark's reduction reads those spans
(`bench/program_spans.py`) and its per-layer metrics read them.

Also the loop's repair: a rider of a chunked prefill whose deadline
expires in the same iteration is retired once, as timed out."""

import math
import pathlib
import re
import sys
import time

import jax
import numpy as np
import pytest

from repro.launch.serve import Server, serve_loop
from repro.models.config import ModelConfig
from repro.runtime import loadgen, snapshot
from repro.runtime.lifecycle import Lifecycle, State

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from bench import harness, program_spans, trace_reduce  # noqa: E402

RECORDED = REPO / "bench" / "tests" / "data" / "qwen3_chat_window.xplane.pb"

# span -> the span it lies inside
PARENT = {
    "serve.admit": "serve.iter", "serve.emit": "serve.iter",
    "serve.retire": "serve.iter", "serve.deadlines": "serve.iter",
    "serve.wait": "serve.iter", "serve.snapshot": "serve.iter",
    "serve.chunk": "serve.iter", "serve.decode": "serve.iter",
    "serve.prefill": "serve.admit",
    **{f"serve.{call}.{part}": f"serve.{call}"
       for call in ("prefill", "chunk", "decode")
       for part in ("prep", "launch", "sync", "post")},
}
NEW_METRICS = ("loop_host_ms.serve", "decode_host_ms.serve",
               "queue_wait_ms.serve")


def _cfg():
    return ModelConfig(name="tiny-spans", family="dense", num_layers=2,
                       d_model=32, d_ff=64, vocab_size=101, num_heads=4,
                       num_kv_heads=2)


def _prompt(rid, n):
    return np.random.default_rng(rid).integers(0, 101, n, dtype=np.int32)


class Scripted:
    """An arrival source: each group of ``(rid, prompt_len, gen_len,
    deadline_s)`` is submitted at its loop step."""

    def __init__(self, groups):
        self.groups = sorted(groups.items())
        self.i = 0

    def pump(self, lc, step):
        while self.i < len(self.groups) and self.groups[self.i][0] <= step:
            for rid, plen, gen, deadline in self.groups[self.i][1]:
                lc.submit(rid, _prompt(rid, plen), gen, deadline_s=deadline)
            self.i += 1

    def exhausted(self):
        return self.i >= len(self.groups)

    def next_arrival_step(self, lc, step):
        return None if self.exhausted() else self.groups[self.i][0]


# Three arrivals at once into two slots (a chunked prefill, then a
# one-slot prefill as a slot frees), then a lone arrival after the loop
# has gone idle (a wait), snapshots every 4 steps.
SCENARIO = {0: [(0, 5, 4, None), (1, 3, 6, None), (2, 7, 3, None)],
            40: [(3, 4, 3, None)]}


def _serve(tmp, trace_dir=None):
    server = Server(_cfg(), 2, 16, autotune_kernels=False)
    lc = Lifecycle(clock=time.perf_counter)
    snaps = snapshot.SnapshotStore(tmp / "snaps", every=4)
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window_start",
                                          seconds=3600.0):
            pass
        stats = serve_loop(server, lc, source=Scripted(SCENARIO),
                           snapshots=snaps)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return lc, stats


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = tmp_path_factory.mktemp("run")
    lc, stats = _serve(root, root / "trace")
    path = program_spans.newest(root / "trace")
    return {"root": root, "lc": lc, "stats": stats,
            "red": trace_reduce.reduce_file(path),
            "spans": program_spans.read_file(path)}


def test_every_span_appears_inside_its_parent(traced):
    spans = traced["spans"]
    kinds = {s.kind for s in spans}
    assert kinds == set(PARENT) | {"serve.iter"}
    for s in spans:
        if s.kind == "serve.iter":
            continue
        assert any(p.kind == PARENT[s.kind] and p.t0 <= s.t0
                   and s.t1 <= p.t1 for p in spans), s


def test_each_call_splits_in_order_and_reads_once(traced):
    """The names and nesting the benchmark's readers depend on: each
    `Server` call holds its four parts once each, in order, its ``.sync``
    is the call's single host read (``reads=1``), and an iteration that
    decodes emits the step's tokens in one ``serve.emit`` after it."""
    spans = traced["spans"]
    parts = ("prep", "launch", "sync", "post")
    assert set(harness.reader("decode_host_ms.serve").PARTS) <= {
        f"serve.decode.{p}" for p in parts}
    assert program_spans.CALLS == ("serve.prefill", "serve.chunk",
                                   "serve.decode")
    for call in program_spans.CALLS:
        calls = [s for s in spans if s.kind == call]
        assert calls, call
        kinds = tuple(f"{call}.{p}" for p in parts)
        for c in calls:
            inner = program_spans.inside(c, spans, kinds)
            assert [s.kind for s in inner] == list(kinds), c
            assert int(inner[2].stats["reads"]) == 1
    for it in (s for s in spans if s.kind == "serve.iter"):
        decode = program_spans.inside(it, spans, ("serve.decode",))
        if decode:
            (emit,) = program_spans.inside(it, spans, ("serve.emit",))
            assert emit.t0 >= decode[0].t1


def test_iter_and_admit_stats(traced):
    lc, spans = traced["lc"], traced["spans"]
    iters = [s for s in spans if s.kind == "serve.iter"]
    assert sum(int(s.stats["tokens"]) for s in iters) == sum(
        len(r.tokens) for r in lc.requests.values())
    assert max(int(s.stats["occupied"]) for s in iters) == 2
    assert [int(s.stats["step"]) for s in iters] == sorted(
        int(s.stats["step"]) for s in iters)
    admits = [s for s in spans if s.kind == "serve.admit"]
    assert sorted(int(s.stats["rid"]) for s in admits) == [0, 1, 2, 3]
    assert all(float(s.stats["wait_ms"]) >= 0.0 for s in admits)
    # the third arrival waited for a slot, the others did not
    wait = {int(s.stats["rid"]): float(s.stats["wait_ms"]) for s in admits}
    assert wait[2] > max(wait[0], wait[1], wait[3])


def test_new_readers_read_the_program_spans(traced, monkeypatch):
    monkeypatch.setattr(harness, "RUN_DIR", traced["root"])
    ctx = harness.Context(spec=None, mix=None, spans=None, w0=0.0,
                          seconds=0.0, trace=traced["red"], peaks=None)
    values = {n: harness.reader(n).read(ctx) for n in NEW_METRICS}
    assert all(v is not None and math.isfinite(v) and v >= 0.0
               for v in values.values()), values
    decode = [s for s in traced["spans"] if s.kind == "serve.decode"]
    assert values["decode_host_ms.serve"] < max(
        s.t1 - s.t0 for s in decode) * 1e3


def test_profiler_on_and_off_serve_the_same(traced, tmp_path):
    lc, stats = _serve(tmp_path)
    # wall-clock and what this process compiled before differ by nature
    volatile = ("first_new_token_s", "compiles")
    assert ({k: v for k, v in stats.items() if k not in volatile}
            == {k: v for k, v in traced["stats"].items()
                if k not in volatile})
    assert {r: q.tokens for r, q in lc.requests.items()} == {
        r: q.tokens for r, q in traced["lc"].requests.items()}


def test_compiles_counts_new_widths_only():
    server = Server(_cfg(), 1, 16, autotune_kernels=False)

    def serve(width):
        lc = Lifecycle()
        for rid in range(2):
            lc.submit(rid, _prompt(rid, width), 3)
        return serve_loop(server, lc)["compiles"]

    assert serve(5) > 0
    assert serve(5) == 0
    assert serve(7) > 0


def test_rider_timed_out_during_a_chunk_is_retired_once():
    """Request 0 decodes from step 0 with a 1.5 s deadline on a 1 s-a-step
    clock; at step 2 two arrivals are admitted as one chunk with it riding,
    and the sweep after admission times it out."""
    server = Server(_cfg(), 3, 32, autotune_kernels=False)
    assert server.can_chunk()
    lc = Lifecycle(clock=loadgen.VirtualClock(1.0))
    source = Scripted({0: [(0, 5, 20, 1.5)],
                       2: [(1, 4, 3, None), (2, 6, 3, None)]})
    stats = serve_loop(server, lc, source=source)
    assert stats["chunked_prefills"] == 1
    assert lc.requests[0].state is State.TIMED_OUT
    assert lc.requests[0].history[-1] == (State.TIMED_OUT, 2)
    assert [lc.requests[r].state for r in (1, 2)] == [State.COMPLETED] * 2
    assert lc.conserved()


# ---------------------------------------------------------------------------
# the reduction, on the recorded chip trace (no program spans) and on a
# made one
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce_file(RECORDED)


def test_recorded_trace_reads_as_before(recorded, tmp_path, monkeypatch):
    """A trace of a program without ``serve.`` spans: the five accepted
    readers read what they read before, the new ones nothing, and the
    split breakdown is the breakdown."""
    assert program_spans.read_file(RECORDED) == ()
    trace = tmp_path / "trace"
    trace.mkdir()
    (trace / RECORDED.name).write_bytes(RECORDED.read_bytes())
    monkeypatch.setattr(harness, "RUN_DIR", tmp_path)
    ctx = harness.Context(
        spec=harness.load_json(REPO / "bench/configs/qwen3_14b_1chip.json"),
        mix=harness.load_json(REPO / "bench/mixes/qwen3_14b.chat.json"),
        spans=harness.Spans(), w0=0.0, seconds=0.0, trace=recorded,
        peaks=harness._peaks(jax.devices()[0], require_tpu=False))
    read = {n: harness.reader(n).read(ctx) for n in (
        "decode_step_ms.serve", "prefill_ms_per_ktok",
        "decode_hbm_share.serve", "step_mfu.prefill", "device_idle.serve",
        *NEW_METRICS)}
    assert read == {
        "decode_step_ms.serve": None, "prefill_ms_per_ktok": None,
        "decode_hbm_share.serve": 27.591475764388157,
        "step_mfu.prefill": 11.87976473567141,
        "device_idle.serve": 29.217604999999892,
        **{n: None for n in NEW_METRICS}}
    assert recorded.breakdown()["idle_gaps"] == [
        ["decode", 0.07007596399999938], ["serve_loop", 0.05873460600000008],
        ["prefill", 0.017277455000000025]]
    split = program_spans.idle_split(recorded, ())
    assert sorted(split.items()) == sorted(
        (k, v) for k, v in recorded.breakdown()["idle_gaps"])


def _by_first_label(split):
    """The split idle time summed by the label before the ``/``."""
    totals: dict[str, float] = {}
    for label, t in split.items():
        first = label.split("/", 1)[0]
        totals[first] = totals.get(first, 0.0) + t
    return totals


def test_idle_split_names_the_innermost_program_span():
    S = trace_reduce.Span
    red = trace_reduce.Reduction(
        ops=[[("fusion.1", 1.0, 2.0), ("fusion.2", 4.0, 5.0),
              ("fusion.1", 8.0, 8.5)]],
        spans=[S("decode", 0.9, 3.95, {}), S("pump", 5.5, 7.5, {})],
        window=(0.0, 10.0))
    program = [S("serve.iter", 0.0, 9.0, {}),
               S("serve.decode", 0.95, 3.9, {}),
               S("serve.decode.post", 2.05, 3.9, {}),
               S("serve.wait", 5.0, 7.9, {}),
               S("serve.emit", 8.6, 9.0, {})]
    split = program_spans.idle_split(red, program)
    assert split == pytest.approx({
        "serve_loop/serve.iter": 1.0,        # 0-1: the decode span has 0.1
        "decode/serve.decode.post": 2.0,     # 2-4: post, decode and iter
        "pump/serve.wait": 3.0,              # 5-8
        "serve_loop": 1.5})                  # 8.5-10: iter 0.5, emit 0.4
    assert _by_first_label(split) == pytest.approx(
        dict(red.breakdown()["idle_gaps"]), rel=1e-12)


# A half-second window of qwen3_14b.chat (one request, 20 decode steps)
# traced on one TPU v5e by `bench/run.py --seconds 0.5 --trace 1`, with
# the program's spans.
RECORDED_SPANS = REPO / "bench" / "tests" / "data" / "qwen3_chat_spans.xplane.pb"


@pytest.fixture(scope="module")
def recorded_spans():
    return (trace_reduce.reduce_file(RECORDED_SPANS),
            program_spans.read_file(RECORDED_SPANS))


def test_recorded_spans_nest_and_split_the_idle_time(recorded_spans):
    red, spans = recorded_spans
    decode = program_spans.starting_in(spans, red.window, "serve.decode")
    assert len(decode) == 20
    for s in spans:
        if s.kind != "serve.iter":
            assert any(p.kind == PARENT[s.kind] and p.t0 <= s.t0
                       and s.t1 <= p.t1 for p in spans), s
    split = program_spans.idle_split(red, spans)
    assert _by_first_label(split) == pytest.approx(
        dict(red.breakdown()["idle_gaps"]), rel=1e-12)
    # the device waits on the host inside decode steps only in their
    # parts: after the step, while the host reads `ok`, and in `post`
    assert split["decode/serve.decode.sync"] > split[
        "decode/serve.decode.post"] > 0.0
    assert sum(t for k, t in split.items() if k.startswith("decode")
               and not k.startswith("decode/serve.decode.")) < 0.01 * sum(
        t for k, t in split.items() if k.startswith("decode"))


def test_recorded_spans_readers(recorded_spans, tmp_path, monkeypatch):
    red, spans = recorded_spans
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace" / RECORDED_SPANS.name).write_bytes(
        RECORDED_SPANS.read_bytes())
    monkeypatch.setattr(harness, "RUN_DIR", tmp_path)
    ctx = harness.Context(spec=None, mix=None, spans=None, w0=0.0,
                          seconds=0.0, trace=red, peaks=None)
    values = {n: harness.reader(n).read(ctx) for n in NEW_METRICS}
    (admit,) = [s for s in spans if s.kind == "serve.admit"]
    assert values["queue_wait_ms.serve"] == float(admit.stats["wait_ms"])
    decode = program_spans.starting_in(spans, red.window, "serve.decode")
    sync = [p for d in decode for p in program_spans.inside(
        d, spans, ("serve.decode.sync",))]
    assert values["decode_host_ms.serve"] == pytest.approx(
        sum(d.t1 - d.t0 for d in decode) * 1e3 / len(decode)
        - sum(p.t1 - p.t0 for p in sync) * 1e3 / len(decode), abs=0.05)
    assert 0.0 < values["loop_host_ms.serve"] < 10.0


# The fused decode kernel's signature: a tpu_custom_call named for its
# pallas_call, whose first operand is the per-row valid lengths it
# prefetches (one s32 per folded row), then q, k and v.
DECODE = (r"^%decode_attention(\.\d+)? = \S+ custom-call\(s32\[\d+\]"
          r"\{[^}]*\} [^,]*, bf16\[.*tpu_custom_call")


def test_recorded_decode_kernel_by_name(recorded_spans):
    red, _ = recorded_spans
    names = [trace_reduce.op_name(n) for n, _, _ in red.ops[0]
             if re.search(DECODE, n)]
    # once per layer (2) of each of the window's 20 decode steps
    assert len(names) == 40
    assert not any(trace_reduce.op_name(n).startswith("checkpoint")
                   for n, _, _ in red.ops[0])
    assert 0.0 < red.kernel_time(DECODE) < red.device_time(("decode",))
