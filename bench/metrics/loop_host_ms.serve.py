"""Host time of the serve loop itself per iteration that emitted a
token: the self time of the window's ``serve.iter`` spans, less the
``serve.wait`` and the `Server` calls (``serve.prefill``, ``serve.chunk``,
``serve.decode``) inside each, averaged over the iterations whose
``tokens`` stat is above 0, in ms.  Emission, retiring slots, the
deadline sweep, admission bookkeeping and the arrival source."""

from bench import program_spans as ps


def read(ctx):
    spans = ps.of_run(ctx)
    iters = [s for s in ps.starting_in(spans, ctx.trace.window, "serve.iter")
             if int(s.stats.get("tokens", 0)) > 0]
    if not iters:
        return None
    out = ps.CALLS + ("serve.wait",)
    own = [(s.t1 - s.t0) - sum(c.t1 - c.t0 for c in ps.inside(s, spans, out))
           for s in iters]
    return sum(own) / len(own) * 1e3
