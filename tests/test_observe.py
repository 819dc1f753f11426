"""Observability substrate tests.

Three load-bearing properties:

1. Disabled is free AND invisible: the default engine holds the
   NULL_TRACER singleton, writes no buffer, and a traced run emits
   exactly the same tokens as an untraced one — for slot+paged layouts,
   dense+8:16-sparse params.
2. The trace IS the metrics: every request's "request_summary" event in
   the written Perfetto trace restates its ``RequestMetrics`` exactly —
   including preemption/resume and prefix-cache-hit lifecycles.
3. The engine's phase spans land in a profiler trace on the host thread,
   nested as ``serving/observe.py`` documents, and the step programs
   lower as ``jit_serve_step`` with the sparse linear's ops named.
"""
import dataclasses
import glob
import json
import math

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import SparsifyConfig
from repro.models import get_model
from repro.runtime.metrics import (RequestMetrics, format_summary, histogram,
                                   histogram_str, percentiles, summarize)
from repro.runtime.telemetry import (Counter, MetricsRegistry, TraceBuffer,
                                     validate_trace_events)
from repro.serving import (NULL_TRACER, NullTracer, SamplingParams,
                           ServingEngine, ServingTracer, Status)
from repro.serving.observe import span

CFG = dataclasses.replace(configs.get_smoke("llama-paper"),
                          name="observe-test", n_layers=2, d_model=128,
                          n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
                          vocab=512, remat=False)
GEN = 6


@pytest.fixture(scope="module")
def dense_params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def sparse_params(dense_params):
    from repro.models.sparse_serving import sparsify_for_serving
    scfg = SparsifyConfig(weight_pattern="8:16", outlier_pattern="16:256",
                          scorer="magnitude", use_smoothquant=False)
    sp, report = sparsify_for_serving(dense_params, scfg)
    assert report["n_layers_sparsified"] > 0
    return sp


def _prompts(n, length, seed=1):
    key = jax.random.PRNGKey(seed)
    return [t.tolist() for t in
            jax.random.randint(key, (n, length), 0, CFG.vocab)]


def _step_compiles(engine) -> dict[str, int]:
    """Executables each of the engine's step programs holds, by the phase
    that dispatches it."""
    a = engine.adapter
    return {"serve.chunk": a._step_fn._cache_size(),
            "serve.decode": a._decode_fn._cache_size()}


def _run(params, prompts, gen, tracer=None, **kw):
    engine = ServingEngine(CFG, params, tracer=tracer, **kw)
    reqs = [engine.submit(p, SamplingParams(max_new_tokens=gen))
            for p in prompts]
    engine.run()
    return engine, reqs


def _summaries(tracer):
    """{request_id: args} of every request_summary event in the buffer."""
    return {e["args"]["id"]: e["args"] for e in tracer.buffer.events
            if e.get("name") == "request_summary"}


def _check_lifecycle_agreement(tracer, reqs):
    """The acceptance property: trace events reconstruct each request's
    lifecycle in exact agreement with its RequestMetrics."""
    summaries = _summaries(tracer)
    for r in reqs:
        m = r.metrics
        s = summaries[r.request_id]
        assert s["status"] == r.status.value
        assert s["admitted"] == m.admitted
        assert s["first_token"] == m.first_token
        assert s["finished"] == m.finished
        assert s["n_tokens"] == m.n_tokens == len(r.tokens)
        assert s["prefill_chunks"] == m.prefill_chunks
        assert s["n_preemptions"] == m.n_preemptions
        assert s["last_preempt_reason"] == m.last_preempt_reason


# --------------------------------------------------------------------------
# disabled tracing: free, and invisible in the token stream
# --------------------------------------------------------------------------

def test_null_tracer_is_default_and_allocation_free(dense_params,
                                                    monkeypatch):
    engine = ServingEngine(CFG, dense_params, n_slots=2, max_len=32)
    assert engine.tracer is NULL_TRACER
    # every hook returns None and holds no state
    t = NullTracer()
    assert t.enabled is False
    assert t.attach(engine) is t
    assert t.end_step(engine, {}) is None
    assert t.instant("x", a=1) is None
    assert not hasattr(t, "buffer")
    # a span under the null tracer is the bare profiler annotation, which
    # records nothing while no profile is being taken
    sp = span("serve.step")
    assert type(sp) is jax.profiler.TraceAnnotation
    with sp:
        pass
    # an untraced run never writes a trace buffer or a counter
    from repro.runtime import telemetry

    def refuse(*a, **k):
        raise AssertionError("the untraced engine wrote observability")
    for cls, names in ((telemetry.TraceBuffer,
                        ("complete", "instant", "counter")),
                       (telemetry.Counter, ("inc",))):
        for name in names:
            monkeypatch.setattr(cls, name, refuse)
    engine.submit(_prompts(1, 8)[0], SamplingParams(max_new_tokens=3))
    engine.run()
    assert engine.finished[0].status is Status.FINISHED


@pytest.mark.parametrize("which", ["dense", "sparse"])
@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_traced_tokens_identical_and_trace_agrees(which, layout, tmp_path,
                                                  dense_params,
                                                  sparse_params):
    params = dense_params if which == "dense" else sparse_params
    prompts = _prompts(3, 16)
    kw = dict(n_slots=3, max_len=32, kv_layout=layout)
    _, ref = _run(params, prompts, GEN, tracer=None, **kw)
    tracer = ServingTracer()
    engine, reqs = _run(params, prompts, GEN, tracer=tracer, **kw)
    assert engine.tracer is tracer
    for rr, r in zip(ref, reqs):
        assert r.status is Status.FINISHED
        assert r.tokens == rr.tokens, "tracing changed the token stream"
    _check_lifecycle_agreement(tracer, reqs)
    # the written file is valid trace_event JSON with the span inventory
    path = tmp_path / "trace.json"
    tracer.write_trace(str(path))
    events = validate_trace_events(json.loads(path.read_text()))
    names = {e["name"] for e in events}
    assert {"serve.step", "serve.schedule", "serve.decode", "serve.emit",
            "serve.sync", "queued", "prefill", "request_summary"} <= names
    # the step span carries the step's stats
    steps = [e for e in events if e["name"] == "serve.step"]
    assert len(steps) == engine.n_steps
    assert sum(e["args"]["decoded"] for e in steps) == \
        sum(len(r.tokens) for r in reqs) - len(reqs)
    # per-step spans live in the engine process, lifecycles in requests
    assert any(e["pid"] == tracer._pid_engine for e in events)
    assert any(e["pid"] == tracer._pid_requests for e in events)


def test_preemption_lifecycle_in_trace(dense_params):
    """A starved paged arena forces preempt-to-queue; the trace carries the
    preemption instants and the summaries agree with RequestMetrics."""
    prompts = _prompts(4, 16, seed=9)
    tracer = ServingTracer()
    engine, reqs = _run(dense_params, prompts, 12, tracer=tracer,
                        n_slots=4, max_len=40, kv_layout="paged",
                        block_size=8, n_blocks=10, prefix_caching=False)
    assert engine.n_preemptions > 0
    assert all(r.status is Status.FINISHED for r in reqs)
    _check_lifecycle_agreement(tracer, reqs)
    summaries = _summaries(tracer)
    preempted = [r for r in reqs if r.metrics.n_preemptions > 0]
    assert preempted
    for r in preempted:
        assert summaries[r.request_id]["last_preempt_reason"] != ""
    # victim instants on both the engine track and the request track
    ev_names = [(e["name"], e.get("cat")) for e in tracer.buffer.events]
    assert ("preempt", "engine") in ev_names
    assert ("preempted", "request") in ev_names
    # counter: every engine-counted preemption is attributed to a reason
    reg = tracer.registry.snapshot()
    total = sum(reg["serving_preemptions_total"].values())
    assert total == engine.n_preemptions


def test_prefix_cache_hit_lifecycle(dense_params):
    """Second submission of the same prompt hits the prefix cache; the
    trace records the lookup, the matched depth, and the summary's
    cached_tokens."""
    prompt = _prompts(1, 16, seed=3)[0]
    tracer = ServingTracer()
    engine = ServingEngine(CFG, dense_params, n_slots=2, max_len=40,
                           kv_layout="paged", block_size=8, n_blocks=16,
                           prefix_caching=True, tracer=tracer)
    r1 = engine.submit(prompt, SamplingParams(max_new_tokens=GEN))
    engine.run()
    r2 = engine.submit(prompt, SamplingParams(max_new_tokens=GEN))
    engine.run()
    assert r1.tokens == r2.tokens
    _check_lifecycle_agreement(tracer, [r1, r2])
    summaries = _summaries(tracer)
    assert summaries[r1.request_id]["cached_tokens"] == 0
    assert summaries[r2.request_id]["cached_tokens"] > 0
    reg = tracer.registry
    assert reg.counter("serving_prefix_cache_lookups_total").get(
        engine=tracer.name, family="dense") == 2
    assert reg.counter("serving_prefix_cache_hits_total").get(
        engine=tracer.name, family="dense") == 1
    assert reg.counter("serving_prefix_cache_hit_tokens_total").get(
        engine=tracer.name, family="dense") == \
        summaries[r2.request_id]["cached_tokens"]
    hits = [e for e in tracer.buffer.events
            if e.get("name") == "prefix_cache" and e["args"]["hit"]]
    assert len(hits) == 1


def test_counters_and_attribution(dense_params):
    """Counters restate the run; compiles come from JAX's own compile
    events, by compiled function, with no extra compile or blocking call
    (the step programs all lower as ``jit_serve_step``)."""
    prompts = _prompts(3, 16)
    tracer = ServingTracer()
    engine, reqs = _run(dense_params, prompts, GEN, tracer=tracer,
                        n_slots=3, max_len=32)
    lb = dict(engine=tracer.name, family="dense")
    reg = tracer.registry
    assert reg.counter("serving_tokens_decoded_total").get(**lb) == \
        sum(len(r.tokens) for r in reqs)
    assert reg.counter("serving_tokens_prefilled_total").get(**lb) == \
        sum(len(p) for p in prompts)
    assert reg.counter("serving_requests_finished_total").get(
        status="finished", **lb) == len(reqs)
    assert reg.counter("serving_steps_total").get(**lb) == engine.n_steps
    # one compile per executable the step programs hold, under the phase
    # that dispatched it: the chunk program's (batch, bucket) variants
    # and the decode program
    n_exec = _step_compiles(engine)
    assert n_exec["serve.chunk"] >= 1 and n_exec["serve.decode"] == 1
    compiles = reg.counter("serving_jit_compiles_total")
    for phase, n in n_exec.items():
        assert compiles.get(fn="jit(serve_step)", phase=phase,
                            engine=tracer.name) == n
    assert sum(compiles.series().values()) >= sum(n_exec.values())
    instants = [e for e in tracer.buffer.events if e.get("name") == "compile"
                and e["args"]["fn"] == "jit(serve_step)"]
    assert sorted(e["args"]["phase"] for e in instants) == sorted(
        p for p, n in n_exec.items() for _ in range(n))
    assert "serving_jit_retraces_total" not in reg.snapshot()
    # prometheus text renders every family with HELP/TYPE headers
    text = tracer.counters_text()
    assert "# TYPE serving_tokens_decoded_total counter" in text
    assert f'engine="{tracer.name}"' in text


def _kv_steps_by_visibility(cursor, nb, bs, P, window=None):
    """KV steps of P*bs tokens that hold a position each row's decode
    query sees (position <= cursor, window-limited, inside the table)."""
    pos = np.arange(nb * bs)
    walked = 0
    for c in cursor:
        seen = pos <= c
        if window is not None:
            seen &= pos > c - window
        walked += len(np.unique(pos[seen] // (P * bs)))
    return walked


@pytest.mark.parametrize("window", [None, 40])
def test_paged_attn_kv_steps_counter(dense_params, window):
    """Hand-built decode batch over tables two walk steps wide: rows whose
    positions cross the first step's edge, a short row and a free row.
    The counter's live and skipped KV steps equal, step by step, what
    the kernel walks at the positions it is handed: the steps holding a
    visible position, of ``rows x ceil(nb / P)``."""
    from repro.serving.paged.paged_attention import kv_walk
    cfg = dataclasses.replace(CFG, window=window)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    tracer = ServingTracer()
    engine = ServingEngine(cfg, params, tracer=tracer, n_slots=4,
                           max_len=320, kv_layout="paged", block_size=8)
    pool = engine.pool
    nb, bs = pool.max_blocks_per_row, pool.block_size
    _, P = kv_walk(1, cfg.n_heads, cfg.n_kv_heads, cfg.hd, bs, nb,
                   pool.k.dtype.itemsize)
    assert P * bs <= 256 < nb * bs and nb % P
    handed = []
    decode = engine.adapter.step_decode

    def record(tokens, active):
        handed.append(np.asarray(pool.pos))
        return decode(tokens, active)

    engine.adapter.step_decode = record
    for n in (250, 10, 255):
        engine.submit(_prompts(1, n)[0], SamplingParams(max_new_tokens=8))
    engine.run()
    assert handed and max(p.max() for p in handed) > 256
    lb = dict(engine=tracer.name, family="dense")
    steps = tracer.registry.counter("serving_paged_attn_kv_steps_total")
    live = sum(_kv_steps_by_visibility(p, nb, bs, P, window)
               for p in handed)
    grid = sum(len(p) * -(-nb // P) for p in handed)
    assert steps.get(kind="live", **lb) == live
    assert steps.get(kind="skipped", **lb) == grid - live > 0


def test_sparse_projections_counter(sparse_params):
    """Each step-program call counts its sparse projections (layers x
    kinds) by the path the sparse linear takes: the portable one here."""
    tracer = ServingTracer()
    engine = ServingEngine(CFG, sparse_params, tracer=tracer, n_slots=2,
                           max_len=64)
    calls = []
    for name in ("step_chunk", "step_decode"):
        def counted(*args, fn=getattr(engine.adapter, name)):
            calls.append(1)
            return fn(*args)
        setattr(engine.adapter, name, counted)
    for p in _prompts(2, 12):
        engine.submit(p, SamplingParams(max_new_tokens=GEN))
    engine.run()
    per_call = CFG.n_layers * 7             # wq wk wv wo w_gate w_up w_down
    assert engine.sparse_paths == {"kernel": 0, "portable": per_call}
    lb = dict(engine=tracer.name, family="dense")
    c = tracer.registry.counter("serving_sparse_projections_total")
    assert c.get(path="portable", **lb) == per_call * len(calls) > 0
    assert c.get(path="kernel", **lb) == 0


# --------------------------------------------------------------------------
# phase spans on the profiler's clock; the step programs' names
# --------------------------------------------------------------------------

def _profiled_serve_line(logdir) -> list[tuple[str, int, int]]:
    """(name, start, end) in ns of every event on the host line that
    holds the engine's ``serve.step`` spans."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if any(n == "serve.step" for n, _, _ in evs):
                return evs
    return []


@pytest.mark.parametrize("traced", [False, True])
def test_phase_spans_nest_in_a_profiler_trace(traced, sparse_params,
                                              tmp_path):
    """A few engine steps under ``jax.profiler``: every ``serve.*`` span
    sits on one host line inside one ``serve.step``, ``serve.sync`` inside
    ``serve.emit``, and no name can be taken for the paged-attention
    kernel.  With a ServingTracer the same spans also reach its buffer."""
    tracer = ServingTracer() if traced else None
    engine = ServingEngine(CFG, sparse_params, n_slots=3, max_len=32,
                           kv_layout="paged", block_size=8, tracer=tracer)
    for p in _prompts(3, 16):
        engine.submit(p, SamplingParams(max_new_tokens=GEN))
    engine.step()                       # compiles before the profile
    engine.step()
    n_phases = len(tracer.buffer.events) if traced else 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            engine.step()
    finally:
        jax.profiler.stop_trace()
    spans = [ev for ev in _profiled_serve_line(tmp_path)
             if ev[0].startswith("serve.")]
    names = [n for n, _, _ in spans]
    assert {"serve.step", "serve.schedule", "serve.decode", "serve.emit",
            "serve.sync"} <= set(names)
    assert not any("paged_attention" in n for n in names)
    assert names.count("serve.step") == 3

    def within(name, s, e):
        return [1 for n, ps, pe in spans if n == name and ps <= s
                and e <= pe]
    for n, s, e in spans:
        if n != "serve.step":
            assert len(within("serve.step", s, e)) == 1, n
        if n == "serve.sync":
            assert len(within("serve.emit", s, e)) == 1
    if traced:
        phases = [e["name"] for e in tracer.buffer.events[n_phases:]
                  if e.get("cat") == "phase"]
        assert sorted(phases) == sorted(names)


def test_step_programs_lower_as_serve_step_with_sparse_linear(
        sparse_params):
    """Both step programs lower under one stable name, and the sparse
    linear's ops carry its scope in their locations only (metadata)."""
    engine = ServingEngine(CFG, sparse_params, n_slots=2, max_len=32,
                           kv_layout="paged", block_size=8)
    a = engine.adapter
    assert a._step_fn.__name__ == a._decode_fn.__name__ == "serve_step"
    lowered = a._decode_fn.lower(
        a.params, *a._arena_args(), a.pool.block_tables, a.pool.pos,
        jax.numpy.zeros((2, 1), np.int32))
    text = lowered.as_text(debug_info=True)
    assert "jit_serve_step" in text
    assert "sparse_linear" in text
    assert "sparse_linear" not in lowered.as_text()


def test_shared_buffer_multi_engine(dense_params):
    """Two engines share one buffer+registry: disjoint pid pairs, distinct
    engine labels (dense and sparse engines share family=dense), and each
    engine's compile counter counts its own compiles only, though both
    tracers are alive while the second engine compiles."""
    buf, reg = TraceBuffer(), MetricsRegistry()
    prompts = _prompts(2, 8, seed=5)
    t1 = ServingTracer(buffer=buf, registry=reg, name="a/slot")
    t2 = ServingTracer(buffer=buf, registry=reg, name="b/paged")
    e1, _ = _run(dense_params, prompts, 4, tracer=t1, n_slots=2,
                 max_len=16)
    e2, _ = _run(dense_params, prompts, 4, tracer=t2, n_slots=2,
                 max_len=16, kv_layout="paged", block_size=8, n_blocks=8)
    compiles = reg.counter("serving_jit_compiles_total")
    for tracer, engine in ((t1, e1), (t2, e2)):
        n_exec = _step_compiles(engine)
        for phase, n in n_exec.items():
            assert compiles.get(fn="jit(serve_step)", phase=phase,
                                engine=tracer.name) == n >= 1
        own = [v for k, v in compiles.series().items()
               if dict(k)["engine"] == tracer.name
               and dict(k)["fn"] == "jit(serve_step)"]
        assert sum(own) == sum(n_exec.values())
    assert {t1._pid_engine, t1._pid_requests}.isdisjoint(
        {t2._pid_engine, t2._pid_requests})
    decoded = reg.counter("serving_tokens_decoded_total")
    assert decoded.get(engine="a/slot", family="dense") == 8
    assert decoded.get(engine="b/paged", family="dense") == 8
    events = validate_trace_events(buf.to_json())
    assert {e["pid"] for e in events} >= {t1._pid_engine, t2._pid_engine}


# --------------------------------------------------------------------------
# telemetry primitives
# --------------------------------------------------------------------------

def test_counter_monotonic_and_labels():
    c = Counter("c_total")
    c.inc()
    c.inc(2, family="x")
    assert c.get() == 1 and c.get(family="x") == 2
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_kind_collision_and_reuse():
    reg = MetricsRegistry()
    c = reg.counter("n_total", "help")
    assert reg.counter("n_total") is c
    with pytest.raises(ValueError):
        reg.gauge("n_total")
    g = reg.gauge("depth")
    g.set(3, q="a")
    g.set(1, q="a")
    assert g.get(q="a") == 1           # gauges are last-write
    snap = reg.snapshot()
    assert snap["n_total"] == {"": 0.0} or snap["n_total"] == {}
    text = reg.prometheus_text()
    assert "# TYPE n_total counter" in text
    assert 'depth{q="a"} 1' in text


def test_trace_buffer_dedup_and_clamp():
    buf = TraceBuffer()
    buf.set_process_name(1, "p")
    buf.set_process_name(1, "p again")    # deduped
    buf.set_thread_name(1, 2, "t")
    buf.set_thread_name(1, 2, "t again")  # deduped
    buf.complete("span", 10.0, -5.0)      # negative dur clamps to 0
    assert len(buf) == 3
    assert buf.events[-1]["dur"] == 0.0
    assert buf.to_json()["displayTimeUnit"] == "ms"


def test_validate_trace_events_accepts_and_rejects():
    ok = {"traceEvents": [
        {"ph": "X", "name": "s", "ts": 0, "dur": 1, "pid": 1, "tid": 0},
        {"ph": "i", "name": "e", "ts": 2, "pid": 1, "tid": 0, "s": "t"},
        {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
         "args": {"name": "p"}}]}
    assert len(validate_trace_events(ok)) == 3
    assert len(validate_trace_events(ok["traceEvents"])) == 3  # bare array
    with pytest.raises(ValueError):
        validate_trace_events({"notTrace": 1})
    with pytest.raises(ValueError):
        validate_trace_events([{"name": "no-ph"}])
    with pytest.raises(ValueError):
        validate_trace_events([{"ph": "X", "name": "s", "ts": 0}])  # no dur
    with pytest.raises(ValueError):
        validate_trace_events([{"ph": "i", "name": "s"}])           # no ts


# --------------------------------------------------------------------------
# runtime/metrics edge cases
# --------------------------------------------------------------------------

def _req(n_tokens, family="", n_pre=0, reason="", chunks=1):
    m = RequestMetrics(family=family, arrival=0.0, admitted=0.1,
                       first_token=0.2, finished=1.0, n_tokens=n_tokens,
                       prefill_chunks=chunks, n_preemptions=n_pre,
                       last_preempt_reason=reason)
    m.itl = [0.01] * max(n_tokens - 1, 0)
    return m


def test_summarize_empty_window():
    s = summarize([], wall_s=0.0)
    assert s["n_requests"] == 0 and s["total_tokens"] == 0
    assert math.isnan(s["tok_per_s"])
    assert math.isnan(s["ttft"]["p50"]) and math.isnan(s["itl"]["p99"])
    assert s["prefill_chunks"]["hist"] == {}
    assert math.isnan(s["prefill_chunks"]["mean"])
    assert s["preemptions"] == {"total": 0, "n_requests_preempted": 0,
                                "max_per_request": 0, "by_reason": {}}
    line = format_summary("empty", s)
    assert "nan" not in line   # no "nanms" segments for an empty window


def test_summarize_all_single_token():
    """Single-token requests have no tpot; the summary line must not print
    nanms for it (the original bug this PR's satellite fixes)."""
    s = summarize([_req(1), _req(1)], wall_s=2.0)
    assert s["n_requests"] == 2
    assert math.isnan(s["tpot"]["p99"])
    assert not math.isnan(s["ttft"]["p99"])   # ttft exists from token one
    line = format_summary("single", s)
    assert "nan" not in line
    assert "ttft" in line and "tpot" not in line


def test_summarize_mixed_family_keys():
    s = summarize([_req(4, family="dense"), _req(4, family="ssm")],
                  wall_s=1.0)
    assert set(s["families"]) == {"dense", "ssm"}
    for fam in ("dense", "ssm"):
        assert s["families"][fam]["n_requests"] == 1
        assert s["families"][fam]["total_tokens"] == 4
    # requests served outside an engine (family unset) get no breakdown
    assert "families" not in summarize([_req(4)], 1.0)
    # a single NAMED family still gets its breakdown (the benchmark keys
    # per-family lines off it even when one family dominates a window)
    assert set(summarize([_req(4, family="dense")], 1.0)["families"]) == \
        {"dense"}


def test_summarize_preemption_block():
    s = summarize([_req(4, n_pre=2, reason="decode_pressure"),
                   _req(4, n_pre=1, reason="prefill_pressure"),
                   _req(4)], wall_s=1.0)
    assert s["preemptions"]["total"] == 3
    assert s["preemptions"]["n_requests_preempted"] == 2
    assert s["preemptions"]["max_per_request"] == 2
    assert s["preemptions"]["by_reason"] == {"decode_pressure": 1,
                                             "prefill_pressure": 1}
    assert "| preempt 3" in format_summary("pre", s)


def test_histogram_numeric_sort():
    h = histogram([10, 2, 10, 1, 2, 10])
    assert list(h) == ["1", "2", "10"]          # numeric, not lexical
    assert h == {"1": 1, "2": 2, "10": 3}
    assert histogram_str(["b", "a", "b"]) == {"a": 1, "b": 2}
    assert list(histogram_str(["b", "a"])) == ["a", "b"]


def test_percentiles_nan_paths():
    p = percentiles([])
    assert math.isnan(p["p50"]) and math.isnan(p["p99"])
    p = percentiles([1.0])
    assert p["p50"] == 1.0 and p["p99"] == 1.0
    # tpot property guards the 0/1-token cases
    assert _req(1).tpot == 0.0
    assert _req(0).tpot == 0.0
    assert _req(5).tpot == pytest.approx((1.0 - 0.2) / 4)
