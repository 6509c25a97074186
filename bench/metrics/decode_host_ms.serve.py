"""Host time of `Server.decode_step` outside its wait for the device:
the ``serve.decode.prep`` (page growth, the active and poison uploads),
``.launch`` (the jitted step's dispatch) and ``.post`` (the token select
and slot bookkeeping) parts of each of the window's ``serve.decode``
spans, everything but ``.sync``, averaged over the steps, in ms."""

from bench import program_spans as ps

PARTS = ("serve.decode.prep", "serve.decode.launch", "serve.decode.post")


def read(ctx):
    spans = ps.of_run(ctx)
    steps = ps.starting_in(spans, ctx.trace.window, "serve.decode")
    if not steps:
        return None
    host = sum(p.t1 - p.t0 for s in steps for p in ps.inside(s, spans, PARTS))
    return host / len(steps) * 1e3
