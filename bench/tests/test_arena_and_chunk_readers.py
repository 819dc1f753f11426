"""The two readers added with the docqa cell: ``arena_ms_per_step`` (the
device time of ops whose output is the whole KV arena or one layer of
it, per step-program call) and ``chunk_mfu`` (the chunk programs' share
of the chip's peak).  On the recorded chip trace of the chat
configuration (``data/serve_spans_steps.json.gz``: a 512-token chunk
beside 10 decode rows, then two decode-only steps, with the layer scan
that sliced, updated and copied the whole ``[24, 2561, 16, 8, 128]``
arena on every step), and on hand-made reductions where they must read
nothing or zero."""
import gzip
import json
from pathlib import Path

import pytest

from bench import registry
from bench import trace_reduce as tr
from bench.harness import Call
from bench.metrics import arena_ms_per_step, chunk_mfu

DATA = Path(__file__).parent / "data" / "serve_spans_steps.json.gz"
CHAT = registry.config("internlm2-1.8b.s816")
PEAKS = registry.peaks("TPU v5 lite")


def _ctx(red, conf=CHAT):
    return tr.Context(conf=conf, peaks=PEAKS, window=None, reduced=red,
                      device={})


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        raw = json.load(f)
    calls = [Call(k, t, [tuple(c) for c in ctx], h, True)
             for k, t, ctx, h in raw["calls"]]
    return tr.reduce(tr.load_saved(str(DATA)), calls, "jit_serve_step")


def test_arena_reader_reads_the_whole_arena_copies(recorded):
    arena = {op: s for op, s in recorded.ops.items()
             if "[24,2561,16,8,128]" in op or "[1,2561,16,8,128]" in op}
    kinds = {op.split(".")[0] for op in arena}
    assert {"copy", "constant_dynamic-update-slice_fusion",
            "constant_dynamic-slice_fusion"} <= kinds
    got = arena_ms_per_step.read(_ctx(recorded))
    assert got == pytest.approx(1e3 * sum(arena.values()) / 4)
    # about 30 ms of each call on the chip: three whole-arena ops and a
    # one-layer slice per program, the chunk program's as well
    assert 20 < got < 40


def test_chunk_mfu_is_a_share_of_the_peak(recorded):
    got = chunk_mfu.read(_ctx(recorded))
    assert 0 < got <= 100


def _reduced(calls, ops):
    return tr.Reduced(window_s=1.0, busy_s=1.0,
                      calls=[(c, s) for c, s in calls], kernel_events={},
                      gaps={}, ops=ops)


def _call(kind, ctx):
    return Call(kind, sum(n for _, n in ctx), list(ctx), len(ctx), True)


def test_readers_read_nothing_without_calls():
    red = _reduced([], {"copy.1 bf16[24,2561,16,8,128]": 1.0})
    assert arena_ms_per_step.read(_ctx(red)) is None
    assert chunk_mfu.read(_ctx(red)) is None


def test_arena_reader_counts_only_arena_shapes():
    """A program that writes the arena in place leaves no whole-arena
    copy: ops of other shapes, the arena's without its trash block
    among them, read zero."""
    ops = {"fusion.3 bf16[24,2560,16,8,128]": 0.5,
           "copy.4 bf16[2561,16,8]": 0.5, "fusion.5 bf16[8,1,92544]": 0.5}
    red = _reduced([(_call("decode", [(9, 1)]), 0.1)], ops)
    assert arena_ms_per_step.read(_ctx(red)) == 0
    ops["fusion.7 bf16[24,2561,16,8,128]"] = 0.004
    ops["dynamic-slice.8 bf16[2561,16,8,128]"] = 0.002
    assert arena_ms_per_step.read(_ctx(red)) == pytest.approx(6.0)


def test_chunk_mfu_reads_nothing_without_a_chunk_call():
    red = _reduced([(_call("decode", [(9, 1)]), 0.1)], {})
    assert chunk_mfu.read(_ctx(red)) is None
    red = _reduced([(_call("decode", [(9, 1)]), 0.1),
                    (_call("chunk", [(0, 512)]), 0.2)], {})
    from bench import counting
    want = 100 * counting.call_flops(CHAT, [(0, 512)], 1) / (
        0.2 * PEAKS["bf16_flops"])
    assert chunk_mfu.read(_ctx(red)) == pytest.approx(want)
