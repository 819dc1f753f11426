"""Share of the traced stretch in which no operation ran on the device:
1 - the union of device-op intervals over the stretch."""


def read(ctx):
    r = ctx.reduced
    return 100.0 * r.idle_share if r.window_s > 0 else None
