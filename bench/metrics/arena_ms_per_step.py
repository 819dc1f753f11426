"""Device time per step-program call of the ops whose output is the whole
KV arena or one layer of it: [L, n_blocks + 1, block_size, KV, head_dim]
(the trash block included), [1, ...] or [n_blocks + 1, ...], shapes
worked out from the configuration.  A layer loop that slices each layer
out of the arena and stacks it back copies the whole arena on every
step; an arena written in place leaves only its scatter here.  Self time
from the traced stretch (``Reduced.ops``), over the traced calls."""
import re

_SHAPE = re.compile(r"\[([\d,]*)\]")


def read(ctx):
    calls = len(ctx.reduced.calls)
    if not calls:
        return None
    m, dep = ctx.conf["model"], ctx.conf["deployment"]
    layer = (dep["n_blocks"] + 1, dep["block_size"], m["n_kv_heads"],
             m["head_dim"])
    shapes = {(m["n_layers"], *layer), (1, *layer), layer}
    t = 0.0
    for op, s in ctx.reduced.ops.items():
        dims = _SHAPE.search(op)
        if dims and tuple(int(d) for d in dims.group(1).split(",")
                          if d) in shapes:
            t += s
    return 1e3 * t / calls
