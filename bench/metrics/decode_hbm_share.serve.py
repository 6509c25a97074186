"""Bytes the window's decode steps need (bench/counts.py: bf16 weights,
each active slot's KV prefix and write) over their device time times the
chip's peak HBM bandwidth, in %.  Device time is the union of device
events inside the benchmark's host span around each `decode_step`."""

from bench import counts


def read(ctx):
    spans = ctx.trace.spans_in_window(("decode",))
    dev = ctx.trace.device_time(("decode",))
    if not spans or dev <= 0:
        return None
    kv = ctx.mix["kv_dtype"]
    need = sum(counts.decode_step_bytes(ctx.spec, kv, int(s.stats["kv_read"]),
                                        int(s.stats["active"]))
               for s in spans)
    return need / (dev * ctx.peaks["hbm_bytes_per_s"]) * 100
