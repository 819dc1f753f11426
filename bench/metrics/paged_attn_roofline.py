"""The paged-attention kernel's least time for its decode calls over its
device time.  Kernel events are the device ops whose name holds
``KERNEL`` (the Pallas kernel lowers to ``paged_attention_pallas.N``
custom calls, one per layer) inside the decode programs; bytes and
operations come from each call's active rows and their contexts
(``counting.paged_attention_decode``)."""
from bench import counting

KERNEL = "paged_attention"


def read(ctx):
    ev = ctx.reduced.kernel_events.get(KERNEL, [])
    t = sum(s for _, s in ev)
    if not t:
        return None
    least = 0.0
    for call, _ in ev:
        b, f = counting.paged_attention_decode(ctx.conf, call.contexts)
        least += counting.least_seconds(b, f, ctx.peaks)
    return 100.0 * least / t
