"""The program's own spans in a profiler trace, and the idle gaps split by
them.

`launch/serve.py` writes a ``serve.<part>`` annotation around each pass
of `serve_loop` (``serve.iter``) and its parts (``serve.admit``,
``serve.emit``, ``serve.retire``, ``serve.deadlines``, ``serve.wait``,
``serve.snapshot``), and around each `Server` call (``serve.prefill``,
``serve.chunk``, ``serve.decode``) and its four parts (``.prep``,
``.launch``, ``.sync``, ``.post``).  They are in the same ``.xplane.pb``
as the device operations and the benchmark's ``bench.<kind>`` spans, on
the same clock.  `bench/trace_reduce.py` keeps the benchmark's spans;
this module reads the program's, for the per-layer metrics that look
inside the program.  A trace of a program without them gives no spans,
and those metrics read nothing.

    python bench/program_spans.py [<trace dir>]

prints, for the newest trace under the directory (by default the one the
last ``--trace 1`` run left), the window's idle gaps labelled as
`Reduction.breakdown` labels them and split by the innermost program
span that covers at least half of each, where the loop's last iteration
ended, the median iteration that emitted a token, the device programs
run per decode step, and the time in each kind of program span.
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import pathlib
import statistics
import sys

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402

PREFIX = "serve."
# The calls into the `Server` that `serve_loop` makes; each waits for the
# device before it returns.
CALLS = ("serve.prefill", "serve.chunk", "serve.decode")
MODULES_LINE = "XLA Modules"


def newest(trace_dir) -> str:
    """The newest ``.xplane.pb`` under a `jax.profiler` trace directory
    (the one `trace_reduce.reduce_dir` reads)."""
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int) -> tuple:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    t0 = ev.start_ns * 1e-9
                    spans.append(trace_reduce.Span(
                        ev.name, t0, t0 + ev.duration_ns * 1e-9,
                        trace_reduce._stats(ev)))
    # a parent before its children where both start at once
    spans.sort(key=lambda s: (s.t0, -s.t1))
    return tuple(spans)


def read_file(path) -> tuple:
    """Every ``serve.`` span of a trace file, sorted by start."""
    return _read(str(path), os.stat(path).st_mtime_ns)


def of_run(ctx) -> tuple:
    """The program spans of the trace a ``--trace 1`` run left (the one
    its `Context.trace` was reduced from)."""
    from bench import harness
    try:
        return read_file(newest(harness.RUN_DIR / "trace"))
    except FileNotFoundError:
        return ()


def inside(outer, spans, kinds) -> list:
    """The spans of ``kinds`` that lie inside ``outer``."""
    i = bisect.bisect_left(spans, outer.t0, key=lambda s: s.t0)
    out = []
    while i < len(spans) and spans[i].t0 <= outer.t1:
        s = spans[i]
        if s is not outer and s.kind in kinds and s.t1 <= outer.t1:
            out.append(s)
        i += 1
    return out


def starting_in(spans, window, kind) -> list:
    lo, hi = window
    return [s for s in spans if s.kind == kind and lo <= s.t0 < hi]


def gaps(red):
    """The device's idle gaps inside the window, in order, as
    `Reduction.breakdown` finds them."""
    lo, hi = red.window
    prev = lo
    for a, b in (red.merged[0] if red.merged else []) + [(hi, hi)]:
        a, b = min(max(a, lo), hi), min(b, hi)
        if a > prev:
            yield prev, a
        prev = max(prev, b)


class _Sweep:
    """The spans that overlap each of a series of intervals given in
    order of their starts."""

    def __init__(self, spans):
        self.spans, self.j, self.active = spans, 0, []

    def overlapping(self, a: float, b: float) -> list:
        while self.j < len(self.spans) and self.spans[self.j].t0 < b:
            self.active.append(self.spans[self.j])
            self.j += 1
        self.active = [s for s in self.active if s.t1 > a]
        return self.active


def idle_split(red, spans) -> dict:
    """Idle seconds by label.  A gap keeps the label `Reduction.label`
    gives it; where a program span covers at least half of the gap, the
    label becomes ``<label>/<innermost such span>``.  Summed by the part
    before the ``/``, the totals are `Reduction.breakdown`'s."""
    bench, program = _Sweep(red.spans), _Sweep(spans)
    out: dict[str, float] = {}
    for a, b in gaps(red):
        cover: dict[str, float] = {}
        for s in bench.overlapping(a, b):
            cover[s.kind] = cover.get(s.kind, 0.0) + (min(s.t1, b)
                                                      - max(s.t0, a))
        kind = max(cover, key=cover.get, default=None)
        label = kind if kind and cover[kind] >= (b - a) / 2 else "serve_loop"
        inner = None
        for s in program.overlapping(a, b):
            if (min(s.t1, b) - max(s.t0, a) >= (b - a) / 2
                    and (inner is None or s.t0 > inner.t0
                         or (s.t0 == inner.t0 and s.t1 < inner.t1))):
                inner = s
        if inner is not None:
            label = f"{label}/{inner.kind}"
        out[label] = out.get(label, 0.0) + (b - a)
    return out


def modules_per_step(path, red) -> dict:
    """Device programs (`XLA Modules` events) run in the window, by name,
    per decode step (the benchmark's ``decode`` spans)."""
    from jax.profiler import ProfileData
    lo, hi = red.window
    steps = len(red.spans_in_window(("decode",)))
    counts: dict[str, int] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                if lo <= ev.start_ns * 1e-9 < hi:
                    name = ev.name.split("(", 1)[0]
                    counts[name] = counts.get(name, 0) + 1
        break
    return {"decode_steps": steps,
            "per_step": {k: v / max(steps, 1) for k, v in
                         sorted(counts.items(), key=lambda kv: -kv[1])}}


def main(argv=None) -> int:
    from bench import harness
    args = sys.argv[1:] if argv is None else argv
    path = newest(args[0] if args else harness.RUN_DIR / "trace")
    red = trace_reduce.reduce_file(path)
    spans = read_file(path)
    lo, hi = red.window
    totals: dict[str, list] = {}
    for s in spans:
        if lo <= s.t0 < hi:
            n, t = totals.get(s.kind, (0, 0.0))
            totals[s.kind] = [n + 1, t + (s.t1 - s.t0)]
    split = sorted(idle_split(red, spans).items(), key=lambda kv: -kv[1])
    iters = starting_in(spans, red.window, "serve.iter")
    emitting = [s.t1 - s.t0 for s in iters
                if int(s.stats.get("tokens", 0)) > 0]
    print(json.dumps({"trace": str(path),
                      "window_s": hi - lo,
                      "busy_s": red.busy_in_window()[0],
                      # the last iteration's end, from the window's start:
                      # a loop that drained early leaves the rest idle
                      "loop_end_s": max((s.t1 for s in iters),
                                        default=lo) - lo,
                      "emitting_iter_ms": {
                          "n": len(emitting),
                          "p50": statistics.median(emitting) * 1e3
                          if emitting else None},
                      "idle_split": split,
                      "modules": modules_per_step(path, red),
                      "program_spans": totals}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
