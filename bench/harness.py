"""One run of one benchmark cell: set-up, the measured window, the drain,
the metrics and the check against the plain reference.

The timed path is the engine `launch/serve.py` runs: `Server` (one-slot
`prefill`, chunked `admit_chunk`, `decode_step`) under `serve_loop`, with
a `Lifecycle` on the wall clock.  The benchmark supplies the arrivals,
the weights (made from the seed), and its own host spans and token clock
around the calls into the program; it changes nothing inside them.

Everything that belongs to one configuration, mix or per-layer metric is
a file of its own, found by the name `BENCHMARK.json` gives it:
``bench/configs/<config>.json``, ``bench/mixes/<traffic>.json`` and
``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
RUN_DIR = ROOT / ".bench"              # runtime files (gitignored)
KV_DTYPES = {"bf16": "bfloat16", "f32": "float32", "int8": "int8"}
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Cells, configurations, mixes
# ---------------------------------------------------------------------------

def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_cell(workload: str, root=ROOT) -> dict:
    """The cell's entry, its configuration file and its mix, by name."""
    bench = load_json(pathlib.Path(root) / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {"bench": bench, "cell": cell,
            "spec": load_json(pathlib.Path(root) / config["file"]),
            "mix": load_json(BENCH / "mixes" / f"{cell['traffic']}.json")}


def model_config(spec: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=spec["name"], family="dense",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        qk_norm=bool(spec.get("qk_norm")), qkv_bias=bool(spec.get("qkv_bias")),
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        remat=spec.get("remat", "none"))


def per_layer_metrics(bench: dict, workload: str) -> list[dict]:
    """The per-layer metrics this cell reports (`workloads`, or every cell
    that reports the end-to-end metric the metric moves)."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            moved = e2e[m["moves"]]
            cells = moved.get("workloads",
                              [c["name"] for c in bench["workloads"]])
        if workload in cells:
            out.append(m)
    return out


def end_to_end_metrics(bench: dict, workload: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if workload in m.get("workloads",
                                 [c["name"] for c in bench["workloads"]])]


def reader(name: str):
    """The reader module of one per-layer metric."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# What the benchmark records around the program
# ---------------------------------------------------------------------------

class TokenClock:
    """The ``journal`` `serve_loop` calls for every emitted token: the wall
    time of each token of each request (a retried request starts over)."""

    def __init__(self):
        self.times: dict[int, list[float]] = {}

    def token(self, rid, i, tok, step):
        now = time.perf_counter()
        ts = self.times.setdefault(rid, [])
        del ts[i:]
        ts.append(now)


@dataclasses.dataclass
class Span:
    kind: str
    t0: float
    t1: float
    tokens: int = 0          # prompt tokens admitted (prefill, chunk)
    active: int = 0          # slots decoding (decode)
    kv_read: int = 0         # KV rows the decode step reads, new one included
    prompts: tuple = ()      # each admitted prompt's length


class Spans:
    """Host spans around the calls into the program, on the wall clock and,
    named ``bench.<kind>``, in the profiler's trace when one is taken."""

    def __init__(self):
        self.spans: list[Span] = []
        self.decode_s: list[tuple[float, float]] = []  # watchdog: (end, s)
        self.admitted: dict[int, float] = {}   # rid -> its prefill's start

    def observe(self, step, seconds):     # the serve loop's watchdog hook
        self.decode_s.append((time.perf_counter(), seconds))

    def wrap(self, server, lc):
        import jax

        def timed(kind, fn, info):
            def call(*a, **kw):
                rec = Span(kind, 0.0, 0.0, **info(*a, **kw))
                stats = {"tokens": rec.tokens, "active": rec.active,
                         "kv_read": rec.kv_read,
                         "prompts": ",".join(map(str, rec.prompts))}
                with jax.profiler.TraceAnnotation(f"bench.{kind}", **stats):
                    rec.t0 = time.perf_counter()
                    try:
                        return fn(*a, **kw)
                    finally:
                        rec.t1 = time.perf_counter()
                        self.spans.append(rec)
            return call

        def prefill_info(slot, rid, prompt, gen_len):
            n = int(np.asarray(prompt).size)
            self.admitted.setdefault(int(rid), time.perf_counter())
            return {"tokens": n, "prompts": (n,)}

        def chunk_info(admits, step=0):
            lens = tuple(int(np.asarray(p).size) for _, _, p, _ in admits)
            for _, rid, _, _ in admits:
                self.admitted.setdefault(int(rid), time.perf_counter())
            return {"tokens": sum(lens), "prompts": lens}

        def decode_info(step=0, use_ref=False):
            kv, act = 0, 0
            for rid in server.slot_req:
                if rid >= 0:
                    req = lc.requests[int(rid)]
                    kv += int(req.prompt.size) + len(req.tokens)
                    act += 1
            return {"active": act, "kv_read": kv}

        server.prefill = timed("prefill", server.prefill, prefill_info)
        server.admit_chunk = timed("chunk", server.admit_chunk, chunk_info)
        server.decode_step = timed("decode", server.decode_step, decode_info)

    def within(self, lo, hi, kinds=None):
        return [s for s in self.spans if lo <= s.t0 < hi
                and (kinds is None or s.kind in kinds)]


class Compiles:
    """Counts programs lowered (compiled, or loaded from the cache) until
    `close`."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


# ---------------------------------------------------------------------------
# Arrivals
# ---------------------------------------------------------------------------

class Source:
    """The arrival source `serve_loop` pumps, open loop: each request is
    submitted once its due time has come, whether or not earlier ones are
    done."""

    def __init__(self, reqs, deadline_s, on_close=None):
        self.reqs = reqs
        self.deadline_s = deadline_s
        self.i = 0
        self.t0 = self.t1 = None
        self.due: dict[int, float] = {}
        self.submitted: dict[int, float] = {}
        self.lag_s = 0.0               # longest wait past a due time
        self.lag_at_s = 0.0            # when in the window it ended
        self.queue_trace: list[tuple[float, int]] = []
        self.on_close = on_close
        self._closed = False
        self._waiting: list = []

    def start(self, t0: float, seconds: float) -> None:
        self.t0, self.t1 = t0, t0 + seconds

    def _submit(self, lc, r, due):
        lc.submit(r.rid, r.prompt, r.output_len - 1,
                  deadline_s=self.deadline_s)
        self.due[r.rid] = due
        self.submitted[r.rid] = time.perf_counter()
        self._waiting.append(lc.requests[r.rid])
        self.i += 1

    def pump(self, lc, step):
        import jax
        with jax.profiler.TraceAnnotation("bench.pump"):
            self._pump(lc)

    def _pump(self, lc):
        from repro.runtime.lifecycle import State
        now = time.perf_counter()
        if not self._closed and now >= self.t1:
            self._closed = True
            if self.on_close is not None:
                self.on_close()
        while (self.i < len(self.reqs)
               and self.t0 + self.reqs[self.i].due_s <= now):
            due = self.t0 + self.reqs[self.i].due_s
            if now - due > self.lag_s:
                self.lag_s, self.lag_at_s = now - due, now - self.t0
            self._submit(lc, self.reqs[self.i], due)
        self._waiting = [r for r in self._waiting if r.state is State.QUEUED]
        self.queue_trace.append((now, len(self._waiting)))

    def exhausted(self) -> bool:
        return self.i >= len(self.reqs)

    def next_arrival_step(self, lc, step):
        """Idle: sleep until the next request is due."""
        if self.exhausted():
            return None
        wait = self.t0 + self.reqs[self.i].due_s - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        return step + 1


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def warm_up(server, buckets, vocab, compiles, rounds: int = 8) -> int:
    """Run every shape and slot the window will use: the one-slot prefill
    of each bucket into each slot, the chunked prefill at each bucket and
    the decode step; then short serve loops over a fixed synthetic stream
    (single arrivals, bursts, arrivals while others decode) until one
    compiles nothing new.  Returns the number of such loops run."""
    from repro.launch.serve import serve_loop
    from repro.runtime.lifecycle import Lifecycle
    rng = np.random.default_rng(0)
    b = server.batch

    def prompt(n):
        return rng.integers(0, vocab, size=n, dtype=np.int32)

    for p in sorted(buckets):
        for s in range(b):
            server.prefill(s, s, prompt(p), 4)
        server.decode_step(0)
        for s in range(b):
            server.release_slot(s)
        if b > 1:
            server.admit_chunk([(s, s, prompt(p), 4) for s in range(b)])
            server.decode_step(0)
            for s in range(b):
                server.release_slot(s)

    class Burst:
        """Submits group ``k`` of requests at loop step ``k * 3``."""

        def __init__(self, groups):
            self.groups, self.i, self.rid = groups, 0, 0

        def pump(self, lc, step):
            while self.i < len(self.groups) and step >= self.i * 3:
                for p in self.groups[self.i]:
                    lc.submit(self.rid, prompt(p), 2)
                    self.rid += 1
                self.i += 1

        def exhausted(self):
            return self.i >= len(self.groups)

        def next_arrival_step(self, lc, step):
            return None if self.exhausted() else step + 1

    for n in range(rounds):
        before = compiles.n
        groups = [[int(p) for p in rng.choice(buckets, size=k)]
                  for k in rng.integers(1, b + 1, size=3 * b)]
        serve_loop(server, Lifecycle(), source=Burst(groups))
        if compiles.n == before:
            return n + 1
    return rounds


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def percentile(vals, q) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q))


def latency(lc, source, clock: TokenClock, drain_end: float) -> dict:
    """TTFT from each request's due time, and the pooled gaps between its
    tokens, over every request due in the window.  A request that did not
    complete counts as late as the drain's end."""
    from repro.runtime.lifecycle import State
    ttft, gaps, failed = [], [], 0
    for rid, due in source.due.items():
        req = lc.requests[rid]
        ts = clock.times.get(rid, [])
        ok = req.state is State.COMPLETED and not any(
            st is State.EVICTED for st, _ in req.history)
        if not ok:
            failed += 1
        ttft.append((ts[0] if ok and ts else drain_end) - due)
        gaps.extend(np.diff(ts).tolist())
    return {"ttft": ttft, "gaps": gaps, "failed": failed,
            "attempted": len(source.due)}


def longest_calls(spans: Spans) -> dict:
    """The longest host span (ms) of each kind of call into the program."""
    out: dict[str, float] = {}
    for s in spans.spans:
        out[s.kind] = max(out.get(s.kind, 0.0), (s.t1 - s.t0) * 1e3)
    return out


def ttft_parts(win) -> dict:
    """Median and 90th percentile (ms) of each part of the time to first
    token: due -> submitted (the source's lag), submitted -> admitted (the
    queue), admitted -> first token (the prefill)."""
    parts = {"lag": [], "queue": [], "prefill": []}
    for rid, due in win.source.due.items():
        sub = win.source.submitted.get(rid)
        adm = win.spans.admitted.get(rid)
        first = win.clock.times.get(rid, [None])[0]
        if None in (sub, adm, first):
            continue
        parts["lag"].append(sub - due)
        parts["queue"].append(adm - sub)
        parts["prefill"].append(first - adm)
    return {k: [percentile(v, 50) * 1e3, percentile(v, 90) * 1e3]
            for k, v in parts.items() if v}


def end_to_end(names, lat, clock, w0, seconds, setup_s) -> dict:
    tokens = sum(1 for ts in clock.times.values() for t in ts
                 if w0 <= t < w0 + seconds)
    values = {
        "output_tok_s": (tokens / seconds, "tokens/s"),
        "ttft_p50_ms": (percentile(lat["ttft"], 50) * 1e3, "ms"),
        "ttft_p90_ms": (percentile(lat["ttft"], 90) * 1e3, "ms"),
        "itl_p50_ms": (percentile(lat["gaps"], 50) * 1e3, "ms"),
        "itl_p95_ms": (percentile(lat["gaps"], 95) * 1e3, "ms"),
        "itl_p99_ms": (percentile(lat["gaps"], 99) * 1e3, "ms"),
        "setup_s": (setup_s, "s"),
    }
    return {n: {"value": values[n][0], "unit": values[n][1]} for n in names}


# ---------------------------------------------------------------------------
# The check against the plain reference
# ---------------------------------------------------------------------------

def sample_for_check(lc, source, seed: int, want_tokens: int) -> list:
    """Requests due in the window that completed, drawn from the seed,
    the longest among them, until they hold ``want_tokens`` served
    tokens."""
    from bench.traffic import rng_for
    from repro.runtime.lifecycle import State
    done = [lc.requests[r] for r in source.due
            if lc.requests[r].state is State.COMPLETED]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), r.prompt.size, -r.rid))
    rest = [r for r in done if r is not longest]
    rng_for(seed, 4).shuffle(rest)
    picked, total = [longest], len(longest.tokens)
    for r in rest:
        if total >= want_tokens:
            break
        picked.append(r)
        total += len(r.tokens)
    return picked


def check(spec, seed, picked, controls=()) -> dict:
    from bench import reference, weights
    w = weights.make(spec, seed)
    rows = [reference.served_gap(w, spec, r.prompt, r.tokens,
                                 controls=controls) for r in picked]
    del w
    out = {"logit_gap": max((r["gap"] for r in rows), default=math.inf),
           "tokens": sum(r["tokens"] for r in rows),
           "requests": len(rows)}
    for q in controls:
        out[f"{q}_gap"] = max(r[f"{q}_gap"] for r in rows)
    return out


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def prepare(cell: dict, root=ROOT, require_tpu=True):
    """Point the caches into the checkout, switch on the compilation
    cache, and find the chips (or raise `NoChip`).  Without
    ``require_tpu`` (tests on the CPU) the compilation cache stays off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(pathlib.Path(root)
                                                  / ".jax_cache")
    RUN_DIR.mkdir(exist_ok=True)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE",
                          str(RUN_DIR / "autotune.json"))
    import jax
    from repro.launch import compile_cache
    if require_tpu:
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return device_info(cell["cell"]["chips"], require_tpu)


@dataclasses.dataclass
class Rig:
    """The system under test, built, loaded and warmed up."""
    server: object
    cfg: object
    mix: dict
    mesh: object
    compiles: Compiles


def build(cell: dict, seed: int) -> Rig:
    """The `Server` the mix asks for, with this seed's weights, every shape
    of the window warmed up."""
    import jax
    import jax.numpy as jnp
    from bench import weights
    from repro.launch import specs as launch_specs
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import Server
    from repro.models import transformer
    from repro.parallel import sharding as shd

    spec, mix = cell["spec"], cell["mix"]
    cfg = model_config(spec)
    buckets = [int(b) for b in mix["prompt_buckets"]]
    compiles = Compiles()
    mesh = make_host_mesh(data=1, model=1)
    with jax.set_mesh(mesh), shd.use_rules(launch_specs.rules_for(mesh)):
        # The Server initialises its own weights; the benchmark's, made
        # from the seed in one jitted call, take their place as they are
        # made, so the chip never holds two sets.
        real_init = transformer.init

        def seeded_init(cfg_, key, dtype=jnp.float32):
            like = jax.eval_shape(lambda: real_init(cfg_, key, dtype))
            return weights.program_params(spec, seed, like)
        transformer.init = seeded_init
        try:
            server = Server(cfg, int(mix["batch"]),
                            max(buckets) + int(mix["output"]["hi"]),
                            prefill_len=max(buckets),
                            kv_dtype=jnp.dtype(KV_DTYPES[mix["kv_dtype"]]))
        finally:
            transformer.init = real_init
        rounds = warm_up(server, buckets, cfg.vocab_size, compiles)
    print(json.dumps({"warm_up": {"loops": rounds,
                                   "programs": compiles.n}}), flush=True)
    return Rig(server, cfg, mix, mesh, compiles)


@dataclasses.dataclass
class Window:
    """What one measured window left behind."""
    lc: object
    source: Source
    clock: TokenClock
    spans: Spans
    stats: dict
    w0: float
    seconds: float
    drain_end: float
    compiles: int
    trace: object = None


WRAPPED = ("prefill", "admit_chunk", "decode_step")


def measure(rig: Rig, reqs, seconds: float, trace: bool = False,
            mutate=None) -> Window:
    """Serve ``reqs`` through the window and drain them."""
    import jax
    from repro.launch import specs as launch_specs
    from repro.launch.serve import serve_loop
    from repro.parallel import sharding as shd
    from repro.runtime.lifecycle import Lifecycle

    server, mix = rig.server, rig.mix
    spans, clock = Spans(), TokenClock()
    lc = Lifecycle(clock=time.perf_counter)
    trace_dir = RUN_DIR / "trace"
    tracing = {"on": False}

    def close_trace():
        if tracing["on"]:
            jax.profiler.stop_trace()
            tracing["on"] = False

    source = Source(reqs, float(mix["deadline_s"]), on_close=close_trace)
    with jax.set_mesh(rig.mesh), \
            shd.use_rules(launch_specs.rules_for(rig.mesh)):
        spans.wrap(server, lc)
        if mutate is not None:
            mutate(server)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tracing["on"] = True
        gc.collect()
        gc.disable()
        before = rig.compiles.n
        w0 = time.perf_counter()
        source.start(w0, seconds)
        with jax.profiler.TraceAnnotation("bench.window_start",
                                          seconds=seconds):
            pass
        try:
            stats = serve_loop(server, lc, watchdog=spans, source=source,
                               journal=clock, max_steps=10**9)
        finally:
            gc.enable()
            close_trace()
            for name in WRAPPED:
                server.__dict__.pop(name, None)
    win = Window(lc, source, clock, spans, stats, w0, seconds,
                 time.perf_counter(), rig.compiles.n - before)
    if trace:
        from bench import trace_reduce
        win.trace = trace_reduce.reduce_dir(trace_dir)
    return win


def release(rig: Rig) -> None:
    """Free the program's device state (before the reference runs)."""
    rig.server.params = rig.server.cache = None
    rig.server = None
    rig.compiles.close()
    gc.collect()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, root=ROOT, require_tpu=True,
             cell=None, mutate=None, controls=(), log=sys.stderr) -> dict:
    """One run; returns the result object the command prints.

    ``cell`` (a `load_cell` result) and ``mutate`` (called with the built
    server before the window) are for tests; ``controls`` (precisions of
    `bench.reference`) adds each lower-precision control's gap to the
    check."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cell or load_cell(workload, root)
    bench, spec, mix = cell["bench"], cell["spec"], cell["mix"]
    devs = prepare(cell, root, require_tpu)
    from bench import traffic

    rig = build(cell, seed)
    reqs = traffic.requests_for(mix, seed, seconds, rig.cfg.vocab_size)
    win = measure(rig, reqs, seconds, trace, mutate)
    setup_s = win.w0 - t_start
    peak = memory_peak(devs)
    release(rig)
    src = win.source
    print(json.dumps({"window": {
        "workload": workload, "seed": seed, "seconds": seconds,
        "compiles_in_window": win.compiles,
        "steps": win.stats["steps"], "generated": win.stats["generated"],
        "chunked_prefills": win.stats["chunked_prefills"],
        "kernel_fallbacks": win.stats["kernel_fallbacks"],
        "arrival_lag_max_s": src.lag_s, "arrival_lag_max_at_s": src.lag_at_s,
        "longest_call_ms": longest_calls(win.spans),
        "queued_max": max((q for _, q in src.queue_trace), default=0),
        "drain_s": win.drain_end - (win.w0 + seconds),
        "ttft_parts_ms": ttft_parts(win)}}), flush=True)

    lat = latency(win.lc, src, win.clock, win.drain_end)
    t_check = time.perf_counter()
    picked = sample_for_check(win.lc, src, seed, int(mix["check_tokens"]))
    gap = (check(spec, seed, picked, controls) if picked
           else {"logit_gap": math.inf, "tokens": 0, "requests": 0})
    limit = float(mix["limits"]["logit_gap"])
    correct = bool(picked) and gap["logit_gap"] <= limit
    print(json.dumps({"check": {**gap, "limit": limit,
                                "seconds": time.perf_counter() - t_check}}),
          flush=True)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": lat["attempted"],
              "failed": lat["failed"]}
    if trace:
        ctx = Context(spec=spec, mix=mix, spans=win.spans, w0=win.w0,
                      seconds=seconds, trace=win.trace,
                      peaks=_peaks(devs[0], require_tpu))
        metrics = {}
        for m in per_layer_metrics(bench, workload):
            value = reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy, window = win.trace.busy_in_window()
        device.update({"busy_s": busy, "window_s": window})
        result["breakdown"] = win.trace.breakdown()
    else:
        names = [m["name"] for m in end_to_end_metrics(bench, workload)]
        metrics = end_to_end(names, lat, win.clock, win.w0, seconds, setup_s)
    # Each control is judged by the same comparison as the program; a
    # sound control comes out not correct.
    checks = {"logit_gap": {"value": gap["logit_gap"], "limit": limit,
                            "correct": correct}}
    for q in controls:
        value = gap[f"{q}_gap"]
        checks[f"{q}_control_gap"] = {
            "value": value, "limit": limit,
            "correct": bool(picked) and value <= limit}
    result.update({"metrics": metrics, "device": device, "checks": checks})
    print(f"check: {gap['tokens']} served tokens of {gap['requests']} "
          f"requests", file=log)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'correct' if c['correct'] else 'not correct'}",
              file=log, flush=True)
    return result


def _peaks(dev, require_tpu):
    from bench.peaks import peaks_for
    if dev.platform != "tpu" and not require_tpu:
        return peaks_for("TPU v5 lite")      # CPU tests of the arithmetic
    return peaks_for(dev.device_kind)


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader reads."""
    spec: dict
    mix: dict
    spans: Spans
    w0: float
    seconds: float
    trace: object
    peaks: dict

    def window_spans(self, kinds):
        return self.spans.within(self.w0, self.w0 + self.seconds, kinds)

    def decode_times(self):
        lo, hi = self.w0, self.w0 + self.seconds
        return [s for end, s in self.spans.decode_s if lo <= end - s < hi]
