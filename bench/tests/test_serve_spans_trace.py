"""A recorded chip trace of the engine with its phase spans and the sparse
linear's named scope (``data/serve_spans_steps.json.gz``: three engine
steps of the chat configuration on one TPU v5e, a 512-token chunk beside
10 decode rows and then two decode-only steps).  Saved like
``decode_chunk_steps.json.gz`` (op names shortened, host events under
20 us dropped but for the ``bench.*`` and ``serve.*`` spans) and with two
fields more: the logged ``calls``, and ``op_scopes``, each op's
``tf_op`` (its op name with scopes) from the trace's event metadata.

It holds what the instrumentation shows on the chip: the step programs
run under ``jit_serve_step`` and match the logged calls, every
``serve.*`` span sits on the benchmark's host line inside one engine
step, nested as ``serving/observe.py`` documents, and the one-hot
rebuild of the sparse linear runs under the ``sparse_linear`` scope."""
import gzip
import json
from pathlib import Path

import pytest

from bench import registry
from bench import trace_reduce as tr
from bench.harness import Call
from bench.metrics import (chunk_ms_per_ktok, decode_mfu, decode_roofline,
                           decode_step_ms, idle_share, paged_attn_roofline)

DATA = Path(__file__).parent / "data" / "serve_spans_steps.json.gz"
SPANS = ("serve.step", "serve.schedule", "serve.chunk", "serve.decode",
         "serve.emit", "serve.sync")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        raw = json.load(f)
    calls = [Call(k, t, [tuple(c) for c in ctx], h, True)
             for k, t, ctx, h in raw["calls"]]
    ev = tr.load_saved(str(DATA))
    red = tr.reduce(ev, calls, "jit_serve_step",
                    ("paged_attention", "sparse_linear"))
    return ev, calls, red, raw["op_scopes"]


def test_step_programs_match_the_calls_under_one_name(recorded):
    ev, calls, red, _ = recorded
    assert [c.kind for c, _ in red.calls] == ["chunk", "decode", "decode",
                                              "decode"]
    ctx = tr.Context(conf=registry.config("internlm2-1.8b.s816"),
                     peaks=registry.peaks("TPU v5 lite"), window=None,
                     reduced=red, device={})
    assert 300 < decode_step_ms.read(ctx) < 400
    assert chunk_ms_per_ktok.read(ctx) > 0
    for reader in (decode_mfu, decode_roofline, paged_attn_roofline,
                   idle_share):
        assert 0 < reader.read(ctx) < 100, reader.__name__
    # no span is taken for the paged-attention kernel, and the scope is
    # not in the op names the reduction matches on
    assert not any("paged_attention" in e.name for e in ev.host)
    assert sum(s for _, s in red.kernel_events["sparse_linear"]) == 0


def _spans(ev):
    return [e for e in ev.host if e.name.startswith("serve.")]


def test_every_span_sits_inside_one_engine_step(recorded):
    ev = recorded[0]
    steps = [e for e in ev.host if e.name == "bench.engine_step"]
    spans = _spans(ev)
    assert {e.name for e in spans} == set(SPANS)
    assert sum(e.name == "serve.step" for e in spans) == len(steps) == 3

    def inside(e, name, pool):
        return [p for p in pool if p.name == name and p.start <= e.start
                and e.end <= p.end]
    for e in spans:
        assert len(inside(e, "bench.engine_step", ev.host)) == 1, e.name
        if e.name != "serve.step":
            assert len(inside(e, "serve.step", spans)) == 1, e.name
        if e.name == "serve.sync":
            assert len(inside(e, "serve.emit", spans)) == 1


def test_sparse_linear_scope_names_the_one_hot_rebuild(recorded):
    """The ops whose ``tf_op`` holds ``sparse_linear`` include the one-hot
    fusions the breakdown lists, each such op runs in the recorded step
    programs, and no paged-attention op carries the scope."""
    ev, _, _, scopes = recorded
    scoped = {n for n, op in scopes.items() if "/sparse_linear/" in op}
    assert not any("paged_attention" in n for n in scoped)
    assert {"fusion.168 bf16[8192,8,256]",
            "fusion.166 bf16[2048,32,256]"} <= scoped
    ran = {e.name for e in ev.ops[0]}
    assert scoped <= ran
