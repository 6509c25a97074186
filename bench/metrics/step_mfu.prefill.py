"""FLOPs the window's admitted prompts need (bench/counts.py: every
layer's matmuls per prompt token, causal attention, the head at each
prompt's last position) over the device time of the prefill and chunk
calls times the chip's peak bf16 FLOP/s, in %: the whole prefill step's
share of the peak, whatever kernels carry it."""

from bench import counts


def _prompts(span):
    return [int(p) for p in str(span.stats.get("prompts", "")).split(",") if p]


def read(ctx):
    spans = ctx.trace.spans_in_window(("prefill", "chunk"))
    dev = ctx.trace.device_time(("prefill", "chunk"))
    if not spans or dev <= 0:
        return None
    flops = sum(counts.prefill_flops(ctx.spec, p)
                for s in spans for p in _prompts(s))
    return flops / (dev * ctx.peaks["bf16_flops_per_s"]) * 100
