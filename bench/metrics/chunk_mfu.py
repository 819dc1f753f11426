"""The chunk programs' share of the chip's peak: the operations their
calls require (``counting.call_flops`` over each call's rows, contexts
and emitting rows) over their device time times peak bf16 FLOP/s, from
the traced stretch."""
from bench import counting


def read(ctx):
    ch = [(call, s) for call, s in ctx.reduced.calls if call.kind == "chunk"]
    t = sum(s for _, s in ch)
    if not t:
        return None
    flops = sum(counting.call_flops(ctx.conf, call.contexts, call.heads)
                for call, _ in ch)
    return 100.0 * flops / (t * ctx.peaks["bf16_flops"])
