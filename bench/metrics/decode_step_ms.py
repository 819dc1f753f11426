"""Device time per decode-program call, from the traced stretch."""


def read(ctx):
    r = ctx.reduced
    t = [s for call, s in r.calls if call.kind == "decode"]
    return 1e3 * sum(t) / len(t) if t else None
