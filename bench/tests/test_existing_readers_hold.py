"""The six per-layer readers the benchmark had before the sparse linear was
named read the recorded chip trace (``data/decode_chunk_steps.json.gz``:
two engine steps of the chat configuration on one TPU v5e) exactly as they
did then.  The readers added beside them change nothing the older ones
read."""
from pathlib import Path

import pytest

from bench import registry
from bench import trace_reduce as tr
from bench.harness import Call
from bench.metrics import (chunk_ms_per_ktok, decode_mfu, decode_roofline,
                           decode_step_ms, idle_share, paged_attn_roofline)

DATA = Path(__file__).parent / "data" / "decode_chunk_steps.json.gz"

# what each reader gave on the recording before any reader was added
BEFORE = {decode_step_ms: 375.134523,
          chunk_ms_per_ktok: 839.8347166666666,
          decode_mfu: 0.08405300157292281,
          decode_roofline: 1.3028981517621525,
          paged_attn_roofline: 0.901895087112325,
          idle_share: 1.4532799156136034}


def _call(kind, tokens=1, ctx=((100, 1),)):
    return Call(kind, tokens, list(ctx), len(ctx), True)


@pytest.mark.parametrize("reader", list(BEFORE), ids=lambda m: m.__name__)
def test_existing_reader_reads_as_before(reader):
    calls = [_call("chunk", tokens=300, ctx=((0, 300),)),
             _call("decode", ctx=[(520, 1)] * 28),
             _call("decode", ctx=[(521, 1)] * 28 + [(300, 1)])]
    kernels = tuple(sorted({m.KERNEL for m in registry_readers()
                            if hasattr(m, "KERNEL")}))
    red = tr.reduce(tr.load_saved(str(DATA)), calls, "jit_traced", kernels)
    ctx = tr.Context(conf=registry.config("internlm2-1.8b.s816"),
                     peaks=registry.peaks("TPU v5 lite"), window=None,
                     reduced=red, device={})
    assert reader.read(ctx) == pytest.approx(BEFORE[reader], rel=1e-12)


def registry_readers():
    """Every per-layer reader the chat cell reports, as the run finds
    them (so the kernels the new readers ask for are timed too)."""
    _, per_layer = registry.metrics_for("internlm2-s816.chat")
    return [registry.metric_reader(m["name"]) for m in per_layer]
