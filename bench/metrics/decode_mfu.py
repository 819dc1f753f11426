"""The decode program's share of the chip's peak: the operations its
calls require (``counting.call_flops`` over the active rows) over their
device time times peak bf16 FLOP/s."""
from bench import counting


def read(ctx):
    dec = [(call, s) for call, s in ctx.reduced.calls if call.kind == "decode"]
    t = sum(s for _, s in dec)
    if not t:
        return None
    flops = sum(counting.call_flops(ctx.conf, call.contexts, call.heads)
                for call, _ in dec)
    return 100.0 * flops / (t * ctx.peaks["bf16_flops"])
