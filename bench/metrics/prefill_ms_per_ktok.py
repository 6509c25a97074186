"""Host-clock time of the window's `Server.prefill` and
`Server.admit_chunk` calls (each ends in a host read of its result) over
the prompt tokens they admitted, in ms per 1000 tokens."""


def read(ctx):
    spans = ctx.window_spans(("prefill", "chunk"))
    tokens = sum(s.tokens for s in spans)
    if not tokens:
        return None
    return sum(s.t1 - s.t0 for s in spans) / tokens * 1e6
