"""Chunk-program device time per 1000 real (unpadded) prompt tokens, from
the traced stretch."""


def read(ctx):
    r = ctx.reduced
    chunks = [(call, s) for call, s in r.calls if call.kind == "chunk"]
    tokens = sum(call.tokens for call, _ in chunks)
    if not tokens:
        return None
    return 1e3 * sum(s for _, s in chunks) / (tokens / 1e3)
