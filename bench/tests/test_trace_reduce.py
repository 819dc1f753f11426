"""The reduction from a profiler trace to per-layer numbers: the union of
device-op intervals, the idle share, kernel time by name inside the decode
programs, the matching of step programs to logged calls, and the
attribution of idle gaps to what the host was doing.  Checked on a
hand-made trace with known answers, and on a recorded chip trace
(``data/decode_chunk_steps.json.gz``: two engine steps of the chat
configuration on one TPU v5e, a 300-token chunk beside 28 decode rows and
then a decode-only step; op names shortened, host events under 20 us
dropped)."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.harness import Call
from bench.metrics import (chunk_ms_per_ktok, decode_step_ms, idle_share,
                           paged_attn_roofline)
from bench import registry

DATA = Path(__file__).parent / "data" / "decode_chunk_steps.json.gz"
PEAKS = registry.peaks("TPU v5 lite")


def _call(kind, tokens=1, ctx=((100, 1),)):
    return Call(kind, tokens, list(ctx), len(ctx), True)


def _kernel_s(red, name):
    return sum(s for _, s in red.kernel_events[name])


def synthetic():
    E = tr.Ev
    # host: two engine steps [0, 100) and [100, 200) (ns); the second
    # holds a sampling call [150, 200)
    host = [E("bench.engine_step", 0, 100), E("bench.engine_step", 100, 100),
            E("sample", 150, 50)]
    # device: a decode program [10, 60) with a kernel [20, 30) and a
    # nested fusion [30, 50); a chunk program [110, 140); the ops of
    # the second chip are busy [0, 200) all through
    mods = [[E("jit_step(1)", 10, 50), E("jit_step(2)", 110, 30)],
            [E("jit_step(1)", 0, 200)]]
    ops = [[E("outer", 10, 50), E("paged_attention_pallas.3", 20, 10),
            E("fusion.1", 30, 20), E("chunk_op", 110, 30)],
           [E("all", 0, 200)]]
    return tr.Events(ops, mods, host)


def test_busy_idle_and_self_time_on_known_intervals():
    calls = [_call("decode"), _call("chunk", tokens=500)]
    red = tr.reduce(synthetic(), calls, "jit_step", ("paged_attention",))
    assert red.window_s == pytest.approx(200e-9)
    # chip 0 busy 50 + 30 ns, chip 1 busy 200 ns: mean 140 ns
    assert red.busy_s == pytest.approx(140e-9)
    assert red.idle_share == pytest.approx(1 - 140 / 200)
    # self time: 'outer' 50 - 10 - 20 = 20 ns, averaged over two chips
    assert red.ops["outer"] == pytest.approx(20e-9 / 2)
    assert _kernel_s(red, "paged_attention") == pytest.approx(10e-9)
    assert [(c.kind, s) for c, s in red.calls] == [
        ("decode", pytest.approx(50e-9)), ("chunk", pytest.approx(30e-9))]


def test_idle_gaps_go_to_the_innermost_host_event():
    red = tr.reduce(synthetic(), [_call("decode"), _call("chunk")],
                    "jit_step", ())
    # chip 0 idle: [0,10) and [60,100) in step 1, [100,110) in step 2,
    # [140,150) in step 2, [150,200) in 'sample'
    assert red.gaps["bench.engine_step"] == pytest.approx(70e-9)
    assert red.gaps["sample"] == pytest.approx(50e-9)
    bd = red.breakdown()
    assert bd["idle_gaps"][0][0] == "bench.engine_step"
    assert len(bd["device_ops"]) <= 10


def test_unmatched_calls_are_an_error():
    """A device trace whose step programs do not match the logged calls
    one for one is an error, not a reading with the calls left out."""
    with pytest.raises(ValueError, match="2 executions of jit_step"):
        tr.reduce(synthetic(), [_call("decode")], "jit_step",
                  ("paged_attention",))


def test_no_device_planes_reads_nothing():
    ev = synthetic()
    red = tr.reduce(tr.Events([], [], ev.host), [_call("decode")],
                    "jit_step", ("paged_attention",))
    assert red.busy_s == 0.0 and red.calls == []


def recorded():
    ev = tr.load_saved(str(DATA))
    calls = [_call("chunk", tokens=300, ctx=((0, 300),)),
             _call("decode", ctx=[(520, 1)] * 28),
             _call("decode", ctx=[(521, 1)] * 28 + [(300, 1)])]
    return ev, calls, tr.reduce(ev, calls, "jit_traced",
                                ("paged_attention",))


def test_recorded_trace_matches_calls_and_bounds_shares():
    ev, calls, red = recorded()
    assert len(red.calls) == 3
    assert [c.kind for c, _ in red.calls] == ["chunk", "decode", "decode"]
    # two steps of a few hundred ms each
    assert 0.5 < red.window_s < 1.5
    assert 0 < red.busy_s <= red.window_s
    dec = [s for c, s in red.calls if c.kind == "decode"]
    # the kernel runs inside the decode programs: less than their time,
    # and at 24 layers a large part of it
    assert 0.2 * sum(dec) < _kernel_s(red, "paged_attention") < sum(dec)
    assert len(red.kernel_events["paged_attention"]) == 2
    assert sum(red.gaps.values()) == pytest.approx(
        red.window_s - sum(e - s for s, e in tr._clip(tr._union(
            (o.start, o.end) for o in ev.ops[0]), *_span(ev))) / 1e9)


def _span(ev):
    st = [e for e in ev.host if e.name == "bench.engine_step"]
    return min(e.start for e in st), max(e.end for e in st)


def test_recorded_trace_readers_stay_below_100_percent():
    ev, calls, red = recorded()
    conf = registry.config("internlm2-1.8b.s816")
    ctx = tr.Context(conf=conf, peaks=PEAKS, window=None, reduced=red,
                     device={})
    share = idle_share.read(ctx)
    assert 0 <= share < 100
    assert 0 < paged_attn_roofline.read(ctx) < 100
    assert decode_step_ms.read(ctx) > 100       # a decode step of ~380 ms
    assert chunk_ms_per_ktok.read(ctx) > 0
