"""From a profiler trace of a stretch of the window to per-layer numbers.

The JAX profiler writes an ``.xplane.pb``; ``load`` keeps what the readers
use as plain event lists (``load_saved`` reads the same lists from JSON,
so the reduction is checked on a small recorded chip trace,
``bench/tests/data``):

  ops      device operations (``XLA Ops`` lines of ``/device:TPU:*``),
           per chip
  modules  device program executions (``XLA Modules`` lines)
  host     the host thread that drives the engine: the benchmark's own
           ``bench.*`` annotations and the profiler's Python function
           events

``reduce`` then works out:

  stretch  from the first ``bench.engine_step`` annotation to the end of
           the last one
  busy     the union of device-op intervals inside the stretch, averaged
           over chips; idle share is 1 - busy / stretch
  calls    each step-program execution matched, in order, to the chunk or
           decode call the benchmark logged for it (the engine syncs on
           its sampled tokens at the end of every step, and the profiler
           starts and stops between steps, so the two sequences are the
           same length and in the same order; where a trace of the device
           holds another count, that is an error, not an empty match)
  kernels  device time of ops whose name holds a kernel's name, inside
           the decode programs
  gaps     every interval of the stretch with no device op, cut where a
           host event starts or ends, each piece attributed to the
           innermost host event open in it
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
from collections import defaultdict


@dataclasses.dataclass
class Ev:
    name: str
    start: float               # ns since the profile started
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Events:
    ops: list                  # [[Ev] per chip]
    modules: list              # [[Ev] per chip]
    host: list                 # [Ev] of the driving thread

    @staticmethod
    def from_json(d: dict) -> "Events":
        dec = lambda evs: [Ev(n, s, t) for n, s, t in evs]  # noqa
        return Events([dec(c) for c in d["ops"]],
                      [dec(c) for c in d["modules"]], dec(d["host"]))


def load(directory: str) -> Events:
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))[-1]
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops.append([Ev(e.name, e.start_ns, e.duration_ns)
                        for e in lines["XLA Ops"].events]
                       if "XLA Ops" in lines else [])
            modules.append([Ev(e.name, e.start_ns, e.duration_ns)
                            for e in lines["XLA Modules"].events]
                           if "XLA Modules" in lines else [])
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                evs = [Ev(e.name, e.start_ns, e.duration_ns)
                       for e in ln.events]
                if any(e.name == "bench.engine_step" for e in evs):
                    host = evs
    return Events(ops, modules, host)


def _union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def short_op(name: str) -> str:
    """'%fusion.168 = bf16[8192,8,256]{...} fusion(...)' ->
    'fusion.168 bf16[8192,8,256]'."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0]
    return f"{head.lstrip('%')} {shape}".strip()[:100]


def self_times(evs: list[Ev]) -> dict[str, float]:
    """Device time of each op name net of the ops nested inside it."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []                  # [event, child time]
    for e in sorted(evs, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            done, child = stack.pop()
            out[short_op(done.name)] += done.dur - child
        if stack:
            stack[-1][1] += e.dur
        stack.append([e, 0.0])
    while stack:
        done, child = stack.pop()
        out[short_op(done.name)] += done.dur - child
    return out


def _innermost(host: list[Ev], starts: list, t: float) -> str:
    """Name of the latest-starting host event open at ``t`` (events of
    one thread nest, so that is the innermost); ``host`` sorted by start,
    ``starts`` their starts."""
    i = bisect.bisect_right(starts, t)
    for h in reversed(host[max(0, i - 5000):i]):
        if h.end > t:
            return h.name
    return "(none)"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    calls: list                # [(Call, device seconds)]
    kernel_events: dict        # kernel name -> [(decode call, seconds)]
    gaps: dict                 # host activity -> idle seconds
    ops: dict                  # op -> self seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(  # noqa
            d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


def reduce(events: Events, calls: list, step_module: str,
           kernels: tuple[str, ...] = ()) -> Reduced:
    """``calls``: the chunk and decode calls logged while tracing;
    ``step_module``: the name the step programs run under (``jit_<fn>``);
    ``kernels``: kernel names to time inside the decode programs."""
    steps = [e for e in events.host if e.name == "bench.engine_step"]
    if not steps:
        return Reduced(0.0, 0.0, [], {k: [] for k in kernels}, {}, {})
    lo, hi = min(e.start for e in steps), max(e.end for e in steps)
    window = hi - lo
    busy, op_self = [], defaultdict(float)
    for chip in events.ops:
        u = _clip(_union((e.start, e.end) for e in chip), lo, hi)
        busy.append(sum(e - s for s, e in u))
        inside = [e for e in chip if lo <= e.start < hi]
        for k, v in self_times(inside).items():
            op_self[k] += v / 1e9 / len(events.ops)
    busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0

    # step-program executions of chip 0, in order, against the logged calls
    chip0 = events.modules[0] if events.modules else []
    mods = sorted((e for e in chip0 if e.name.startswith(step_module + "(")),
                  key=lambda e: e.start)
    if events.modules and len(mods) != len(calls):
        raise ValueError(
            f"{len(mods)} executions of {step_module} in the trace against "
            f"{len(calls)} logged chunk and decode calls")
    matched = list(zip(calls, mods))
    ops0 = sorted(events.ops[0] if events.ops else [], key=lambda e: e.start)
    starts0 = [e.start for e in ops0]
    kernel_events = {k: [] for k in kernels}
    out_calls = []
    for call, mod in matched:
        inner = ops0[bisect.bisect_left(starts0, mod.start):
                     bisect.bisect_left(starts0, mod.end)]
        out_calls.append((call, mod.dur / 1e9))
        if call.kind != "decode":
            continue
        for k in kernels:
            t = sum(e.dur for e in inner if k in e.name) / 1e9
            kernel_events[k].append((call, t))

    # idle gaps inside the stretch, by the innermost host event open
    u0 = _clip(_union((e.start, e.end) for e in ops0), lo, hi)
    gaps, t = [], lo
    for s, e in u0:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    host = sorted(events.host, key=lambda e: e.start)
    hstarts = [h.start for h in host]
    cuts = sorted({t for h in host for t in (h.start, h.end)})
    by_what: dict[str, float] = defaultdict(float)
    for s, e in gaps:
        # split the gap where a host event starts or ends, so each piece
        # has one innermost host event
        inner = cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)]
        for a, b in zip([s, *inner], [*inner, e]):
            by_what[_innermost(host, hstarts, (a + b) / 2)] += (b - a) / 1e9
    return Reduced(window / 1e9, busy_s, out_calls, kernel_events,
                   dict(by_what), dict(op_self))


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    conf: dict
    peaks: dict
    window: object             # harness.Window
    reduced: Reduced
    device: dict


def load_saved(path: str) -> Events:
    with (gzip.open if path.endswith(".gz") else open)(path, "rt") as f:
        return Events.from_json(json.load(f))
