"""From a profiler trace of the window to the numbers the per-layer
metrics read.

The trace is JAX's ``.xplane.pb`` (`jax.profiler.start_trace`), read with
`jax.profiler.ProfileData` and nothing else.  Two kinds of events matter:

* device operations: the events of each TPU plane's ``XLA Ops`` line;
* the benchmark's host spans: the ``bench.<kind>`` annotations the harness
  puts around each call into the program (``prefill``, ``chunk``,
  ``decode``, ``pump``), with their counts as stats, and the zero-length
  ``bench.window_start`` marker whose ``seconds`` stat gives the window.

Busy time is the union of the device intervals; a span's device time is
that union clipped to the span (the program's calls end in a host read of
their result, so a call's device work lies inside its span); a kernel's
time is the sum of its events' durations.  Times are seconds on the
trace's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# Control flow whose body ops are events of their own: left out of the
# op ranking so that no time counts twice.
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


@dataclasses.dataclass
class Span:
    kind: str
    t0: float
    t1: float
    stats: dict


@dataclasses.dataclass
class Reduction:
    ops: list          # per chip: [(name, t0, t1)]
    spans: list        # [Span], host, sorted by start
    window: tuple      # (t0, t1)

    def __post_init__(self):
        self.merged = [union((a, b) for _, a, b in chip) for chip in self.ops]

    def spans_in_window(self, kinds) -> list:
        lo, hi = self.window
        return [s for s in self.spans if s.kind in kinds and lo <= s.t0 < hi]

    def busy_in_window(self) -> tuple[float, float]:
        """(device busy seconds averaged over the chips, window seconds)."""
        lo, hi = self.window
        busy = [covered(m, lo, hi) for m in self.merged]
        return sum(busy) / max(len(busy), 1), hi - lo

    def device_time(self, kinds) -> float:
        """Device busy time inside the window's spans of ``kinds``,
        averaged over the chips."""
        spans = self.spans_in_window(kinds)
        per_chip = [sum(covered(m, s.t0, s.t1) for s in spans)
                    for m in self.merged]
        return sum(per_chip) / max(len(per_chip), 1)

    def kernel_time(self, pattern: str) -> float:
        """Summed device time of the window's ops whose name matches."""
        lo, hi = self.window
        rx = re.compile(pattern)
        total = sum(b - a for chip in self.ops for name, a, b in chip
                    if lo <= a < hi and rx.search(name))
        return total / max(len(self.ops), 1)

    def label(self, a: float, b: float) -> str:
        """What the host was doing in ``[a, b]``: the kind of span that
        covers most of it, if that is at least half, else the rest of the
        serve loop."""
        cover: dict[str, float] = {}
        for s in self.spans:
            if s.t0 < b and s.t1 > a:
                cover[s.kind] = cover.get(s.kind, 0.0) + (min(s.t1, b)
                                                          - max(s.t0, a))
        kind = max(cover, key=cover.get, default=None)
        return kind if kind and cover[kind] >= (b - a) / 2 else "serve_loop"

    def breakdown(self, top: int = 10) -> dict:
        """Inside the window: the device ops that took most time, and the
        device's idle time summed by what the host was doing meanwhile
        (each gap between device intervals goes to the host span that
        covers most of it)."""
        lo, hi = self.window
        by_name: dict[str, float] = {}
        for name, a, b in (self.ops[0] if self.ops else []):
            short = op_name(name)
            if lo <= a < hi and not CONTAINER.match(short):
                by_name[short] = by_name.get(short, 0.0) + (b - a)
        idle: dict[str, float] = {}
        prev = lo
        for a, b in (self.merged[0] if self.merged else []) + [(hi, hi)]:
            a, b = min(max(a, lo), hi), min(b, hi)
            if a > prev:
                kind = self.label(prev, a)
                idle[kind] = idle.get(kind, 0.0) + (a - prev)
            prev = max(prev, b)
        return {"device_ops": _top(by_name, top),
                "idle_gaps": _top(idle, top)}


def _top(totals: dict, n: int) -> list:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:
        return {}


def reduce_file(path) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, spans, window = [], [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            chip = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip.extend((ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9)
                                for ev in line.events)
            ops.append(chip)
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                kind = ev.name[len(SPAN_PREFIX):]
                t0 = ev.start_ns * 1e-9
                st = _stats(ev)
                if kind == "window_start":
                    window = (t0, t0 + float(st["seconds"]))
                else:
                    spans.append(Span(kind, t0,
                                      t0 + ev.duration_ns * 1e-9, st))
    if window is None:
        raise ValueError(f"{path}: no bench.window_start marker")
    spans.sort(key=lambda s: s.t0)
    return Reduction(ops=ops, spans=spans, window=window)


def reduce_dir(trace_dir) -> Reduction:
    """The newest ``.xplane.pb`` under a `jax.profiler` trace directory."""
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1])
