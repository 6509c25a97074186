"""Median time a request waited in the admission queue: the ``wait_ms``
stat of each request's first ``serve.admit`` span (submission to the start
of its admission, on the lifecycle's clock), over the requests submitted
inside the window, in ms."""

import statistics

from bench import program_spans as ps


def read(ctx):
    lo, hi = ctx.trace.window
    first = {}
    for s in ps.of_run(ctx):
        if s.kind == "serve.admit":
            first.setdefault(int(s.stats["rid"]), s)
    waits = [float(s.stats["wait_ms"]) for s in first.values()
             if lo <= s.t0 - float(s.stats["wait_ms"]) * 1e-3 < hi]
    return statistics.median(waits) if waits else None
