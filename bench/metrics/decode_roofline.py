"""Least decode time over measured decode time.  The least time of a
call is the larger of its bytes over peak HBM bandwidth and its
operations over peak FLOP/s; its bytes are the deployed buffers of every
weight the step reads, one embedding row per active row, and the KV of
the active rows at their contexts (``counting.decode_bytes``)."""
from bench import counting


def read(ctx):
    dec = [(call, s) for call, s in ctx.reduced.calls if call.kind == "decode"]
    t = sum(s for _, s in dec)
    if not t:
        return None
    least = sum(counting.least_seconds(
        counting.decode_bytes(ctx.conf, call.contexts),
        counting.call_flops(ctx.conf, call.contexts, call.heads), ctx.peaks)
        for call, _ in dec)
    return 100.0 * least / t
