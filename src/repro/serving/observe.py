"""Serving observability: the engine's phase spans, request/step tracing
and the serving counter set — free when no profile is being taken.

**Phase spans** (``span``).  The engine marks its phases with
``span(name, tracer)``, one context manager per phase.  It always opens
a ``jax.profiler.TraceAnnotation(name)``, so a profiler trace that is
running (``jax.profiler.start_trace``) holds the phase on the host thread
that drives the engine, on the device trace's clock, beside the device
programs the phase launched.  When no profile is being taken the
annotation records nothing: a span does no host sync, writes no buffer
and formats no string.  The names, with their nesting:

  ``serve.step``          the whole of ``engine.step``
    ``serve.schedule``    evict expired requests, plan and admit
    ``serve.chunk``       one chunk-group dispatch and its last-logit gather
    ``serve.draft``       the speculative drafter's proposals
    ``serve.decode``      prepare the decode rows, build the inputs and
                          dispatch the decode (the speculative verify)
    ``serve.emit``        the sampler call, streaming tokens, retiring
                          requests
      ``serve.sync``      exactly the blocking reads of sampled tokens and
                          logprobs (the drafter's reads of its proposals
                          stay in ``serve.draft``)

The step programs themselves lower as ``jit_serve_step``
(``families._suspend``), and the sparse linear's device ops carry the
scope ``sparse_linear`` in their op-name metadata, which a device profile
keeps as each op's ``tf_op`` (``models/sparse_serving.sparse_apply``).

**The engine's tracer.**  The engine takes a ``tracer=``.  The default
is ``NULL_TRACER``, a singleton whose every hook is a no-op and whose
``enabled`` flag is False; every other call site in the hot path is
guarded by ``if tracer.enabled`` (tests/test_observe.py pins both the
identity and the token-identity of traced vs untraced runs).  Pass a
``ServingTracer`` (``launch/serve.py --trace-out``) and it also writes:

1. **Spans** (``runtime/telemetry.py`` ``TraceBuffer``, Chrome/Perfetto
   ``trace_event`` JSON — load the written file in ``ui.perfetto.dev``),
   on the engine's clock:

   - an *engine* process: every ``span`` above as a "phase" span (the
     ``serve.step`` span carries the step's stats), plus instants for
     compiles, preemptions (victim + reason), and prefix-cache lookups
     (matched-block depth);
   - a *requests* process: one thread per request id carrying its
     lifecycle spans — "queued" (arrival -> admitted, re-opened on
     preemption), "prefill" (admitted -> prefill complete, with "chunk"
     instants per chunk), "decode" (first-token eligibility -> finish) —
     and a final "request_summary" instant whose args restate the
     request's ``RequestMetrics`` (admit time, chunk count, token count,
     preemptions), so traces and summaries come from one event stream
     and can be cross-checked exactly.

2. **Counters/gauges** (``MetricsRegistry``): tokens prefilled/decoded,
   requests finished/evicted, preemptions by reason, compiles by the
   compiled function's name and the ``serve.*`` phase that dispatched
   them (JAX's own ``backend_compile_duration`` events, heard through
   ``jax.monitoring`` while this tracer's engine is inside a step: no
   blocking call and no extra compile; the drafter's compiles fall under
   ``serve.draft``), prefix-cache lookups/hit-tokens, the paged-attention
   kernel's live and skipped KV steps per decode step (the kernel's own
   geometry over the positions mirror, ``paged_attention.kv_steps``),
   speculative draft/accept/emit token counters (plus a per-step
   acceptance-rate gauge — the live Divergent-Token probe), and per-step
   gauges (queue depth, running, pool occupancy, budget utilization) —
   all labelled by engine and model family — rendered as a Prometheus
   text snapshot (``counters_text()``) and sampled into the trace as "C"
   counter events every ``sample_every`` steps.

Multiple engines can share one ``TraceBuffer`` and one
``MetricsRegistry`` (the benchmark traces dense/sparse x slot/paged runs
into a single file): give each engine its own ``ServingTracer`` with the
shared ``buffer=``/``registry=`` — each tracer allocates its own
process-id pair and labels its metrics by engine name and family.

Timestamps come from the engine's injected clock (``attach`` adopts it
unless the tracer was built with an explicit ``clock=``), so virtual-time
tests produce exact, deterministic traces.
"""
from __future__ import annotations

import time
import weakref

import jax
from jax.profiler import TraceAnnotation

from ..runtime.telemetry import MetricsRegistry, TraceBuffer


class NullTracer:
    """The disabled tracer: every hook is a no-op.  ``enabled`` is False
    so engine call sites skip even argument construction; the hooks still
    exist so an unguarded call is harmless rather than fatal."""
    enabled = False

    def attach(self, engine, name=""):
        return self

    def end_step(self, engine, stats):
        pass

    def instant(self, name, **args):
        pass

    def on_submit(self, req):
        pass

    def on_admit(self, req, n_cached=0, cache_lookup=False):
        pass

    def on_chunk(self, req, cursor, take):
        pass

    def on_decode_kv(self, walked, grid):
        pass

    def on_prefill_complete(self, req):
        pass

    def on_preempt(self, req, reason):
        pass

    def on_finish(self, req):
        pass

    def on_evict(self, req):
        pass

    def on_withdraw(self, req):
        pass


NULL_TRACER = NullTracer()

_ENGINE_TID = 0


def span(name: str, tracer=NULL_TRACER):
    """Context manager marking one engine phase: a profiler annotation
    ``name`` (recorded only while a profile is being taken), and with an
    enabled ``tracer`` also a "phase" span in its buffer."""
    if tracer.enabled:
        return _Phase(tracer, name)
    return TraceAnnotation(name)


class _Phase:
    """An enabled tracer's span: the profiler annotation, and the phase
    written to the tracer's buffer on exit (with any args ``end_step``
    left on it)."""
    __slots__ = ("tracer", "name", "ann", "t0", "args")

    def __init__(self, tracer, name):
        self.tracer, self.name = tracer, name
        self.ann = TraceAnnotation(name)
        self.args = None

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = self.tracer.clock()
        self.tracer._phases.append(self)
        return self

    def __exit__(self, *exc):
        tr = self.tracer
        tr._phases.pop()
        ts0 = tr._ts(self.t0)
        tr.buffer.complete(self.name, ts0, tr._ts() - ts0,
                           pid=tr._pid_engine, tid=_ENGINE_TID, cat="phase",
                           args=self.args)
        self.ann.__exit__(*exc)
        return False


# JAX reports each XLA compile through jax.monitoring; the event wraps
# its compile_or_get_cached, so a persistent-cache load reports too.  One
# process-wide listener hands the event to the tracer whose engine is
# inside a step: compiles run synchronously in the phase that dispatched
# them, and the engines of one process step one at a time.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_TRACERS: weakref.WeakSet = weakref.WeakSet()
_compile_listener_on = False


def _on_duration_event(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        for tracer in list(_COMPILE_TRACERS):
            if tracer._phases:
                tracer._on_compile(str(kw.get("fun_name", "")),
                                   tracer._phases[-1].name, duration)


def _hear_compiles(tracer) -> None:
    global _compile_listener_on
    if not _compile_listener_on:
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _compile_listener_on = True
    _COMPILE_TRACERS.add(tracer)


class ServingTracer:
    """The enabled tracer; see the module docstring for what it records.

    ``buffer``/``registry`` default to fresh private instances; pass
    shared ones to merge several engines into one trace/counter set.
    ``clock`` defaults to adopting the engine's clock at ``attach`` (falling
    back to ``time.monotonic``); pass the engine's virtual clock explicitly
    only when events must be stamped before an engine exists.
    ``sample_every`` thins the per-step counter samples written into the
    trace (the registry itself is always current).
    """

    enabled = True

    def __init__(self, *, buffer: TraceBuffer | None = None,
                 registry: MetricsRegistry | None = None,
                 clock=None, sample_every: int = 1, name: str = ""):
        self.buffer = buffer if buffer is not None else TraceBuffer()
        self.registry = registry if registry is not None else MetricsRegistry()
        self.clock = clock
        self.sample_every = max(int(sample_every), 1)
        self.t0: float | None = None
        self.name = name
        self.family = ""
        self._pid_engine: int | None = None
        self._pid_requests: int | None = None
        # open spans: engine phases (innermost last), per-request state
        self._phases: list[_Phase] = []
        self._req_open: dict[int, dict[str, float]] = {}
        self._req_cached: dict[int, int] = {}
        self._counters_made = False

    # --------------------------------------------------------------- setup
    def attach(self, engine, name: str = "") -> "ServingTracer":
        """Bind this tracer to an engine: adopt its clock (unless one was
        given), allocate the engine/requests process ids, and register the
        serving counter set labelled by the engine's family."""
        if self.clock is None:
            self.clock = getattr(engine, "_clock", time.monotonic)
        if self.t0 is None:
            self.t0 = self.clock()
        self.family = getattr(getattr(engine, "cfg", None), "family", "")
        self.name = (self.name or name
                     or (f"{self.family}/{getattr(engine, 'kv_layout', '')}"
                         if self.family else "engine"))
        base = len(self.buffer._named_processes)
        self._pid_engine = 2 * base + 1
        self._pid_requests = 2 * base + 2
        self.buffer.set_process_name(self._pid_engine,
                                     f"engine {self.name}")
        self.buffer.set_process_name(self._pid_requests,
                                     f"requests {self.name}")
        self.buffer.set_thread_name(self._pid_engine, _ENGINE_TID, "steps")
        self._make_counters()
        _hear_compiles(self)
        return self

    def _make_counters(self):
        r = self.registry
        self.c_prefilled = r.counter(
            "serving_tokens_prefilled_total",
            "prompt tokens written through prefill chunks")
        self.c_decoded = r.counter(
            "serving_tokens_decoded_total", "generated tokens emitted")
        self.c_finished = r.counter(
            "serving_requests_finished_total",
            "requests retired, by terminal status")
        self.c_preempted = r.counter(
            "serving_preemptions_total",
            "requests preempted back to the queue, by pressure source")
        self.c_compiles = r.counter(
            "serving_jit_compiles_total",
            "XLA compiles (persistent compile-cache loads included) JAX "
            "reported inside this engine's steps, by compiled function "
            "and the innermost serve.* phase that dispatched them")
        self.c_cache_lookups = r.counter(
            "serving_prefix_cache_lookups_total",
            "prefix-cache lookups at admission")
        self.c_cache_hits = r.counter(
            "serving_prefix_cache_hits_total",
            "admissions that matched at least one cached block")
        self.c_cache_hit_tokens = r.counter(
            "serving_prefix_cache_hit_tokens_total",
            "prompt tokens skipped via prefix-cache matches")
        self.c_steps = r.counter("serving_steps_total", "engine steps run")
        self.c_spec_drafted = r.counter(
            "serving_spec_tokens_drafted_total",
            "draft tokens proposed to the speculative verify step")
        self.c_spec_accepted = r.counter(
            "serving_spec_tokens_accepted_total",
            "draft tokens the target model accepted")
        self.c_spec_emitted = r.counter(
            "serving_spec_tokens_emitted_total",
            "tokens emitted by speculative steps (accepted + bonus)")
        self.c_kv_steps = r.counter(
            "serving_paged_attn_kv_steps_total",
            "KV steps of one layer's paged-attention kernel per decode "
            "step (every layer walks the same): live ones it walks, "
            "skipped ones past what each row can see")
        self.g_spec_accept = r.gauge(
            "serving_spec_acceptance_rate",
            "per-step draft acceptance rate (accepted / drafted)")
        self.g_queue = r.gauge("serving_queue_depth", "requests queued")
        self.g_running = r.gauge("serving_running", "requests in slots")
        self.g_pool_free = r.gauge(
            "serving_pool_free", "free concurrency units (slots/rows)")
        self.g_blocks_free = r.gauge(
            "serving_kv_blocks_free", "free KV blocks (paged layout)")
        self.g_cache_entries = r.gauge(
            "serving_prefix_cache_entries", "live prefix-cache entries")
        self.g_budget_util = r.gauge(
            "serving_budget_utilization",
            "prefill tokens spent this step / token budget")
        self._counters_made = True

    def _labels(self) -> dict:
        """Shared metric labels: several engines can feed one registry
        (the benchmark's dense/sparse x slot/paged grid), so every series
        carries both the engine name and its family."""
        return {"engine": self.name, "family": self.family}

    # ---------------------------------------------------------- time/pids
    def _ts(self, t: float | None = None) -> float:
        if self.t0 is None:
            self.t0 = self.clock() if self.clock else 0.0
        t = self.clock() if t is None else t
        return (t - self.t0) * 1e6

    # ------------------------------------------------------- step counters
    def end_step(self, engine, stats: dict) -> None:
        """Count a finished step (called inside its ``serve.step`` span,
        which then carries ``stats``)."""
        now = self.clock()
        lb = self._labels()
        self.c_steps.inc(**lb)
        self.c_prefilled.inc(stats.get("prefill_tokens", 0), **lb)
        util = (stats.get("prefill_tokens", 0)
                / max(engine.token_budget, 1))
        self.g_budget_util.set(util, **lb)
        queue_depth = len(engine.queue)
        running = len(engine.running)
        self.g_queue.set(queue_depth, **lb)
        self.g_running.set(running, **lb)
        self.g_pool_free.set(engine.pool.n_free, **lb)
        sample = {"queue_depth": queue_depth, "running": running,
                  "pool_free": engine.pool.n_free,
                  "budget_utilization": round(util, 4)}
        if "spec_drafted" in stats:
            drafted = stats["spec_drafted"]
            accepted = stats.get("spec_accepted", 0)
            self.c_spec_drafted.inc(drafted, **lb)
            self.c_spec_accepted.inc(accepted, **lb)
            self.c_spec_emitted.inc(stats.get("spec_emitted", 0), **lb)
            rate = accepted / drafted if drafted else 0.0
            self.g_spec_accept.set(rate, **lb)
            sample["spec_acceptance_rate"] = round(rate, 4)
        if engine.kv_layout == "paged":
            pool = engine.pool
            self.g_blocks_free.set(pool.blocks.n_free, **lb)
            sample["blocks_free"] = pool.blocks.n_free
            if pool.prefix_cache is not None:
                self.g_cache_entries.set(len(pool.prefix_cache), **lb)
                sample["prefix_cache_entries"] = len(pool.prefix_cache)
        if (engine.n_steps - 1) % self.sample_every == 0:
            self.buffer.counter("engine", self._ts(now), sample,
                                pid=self._pid_engine, tid=_ENGINE_TID)
        if self._phases:
            self._phases[-1].args = dict(stats)

    def _on_compile(self, fun_name: str, phase: str, seconds: float) -> None:
        self.c_compiles.inc(fn=fun_name, phase=phase, engine=self.name)
        self.instant("compile", fn=fun_name, phase=phase,
                     seconds=round(seconds, 6))

    def instant(self, name: str, **args) -> None:
        self.buffer.instant(name, self._ts(), pid=self._pid_engine,
                            tid=_ENGINE_TID, cat="engine", args=args or None)

    # ----------------------------------------------------- request spans
    def _req_begin(self, req, span: str, t: float) -> None:
        self._req_open.setdefault(req.request_id, {})[span] = t

    def _req_end(self, req, span: str, t: float,
                 args: dict | None = None) -> None:
        open_spans = self._req_open.get(req.request_id, {})
        t0 = open_spans.pop(span, None)
        if t0 is None:
            return
        ts0 = self._ts(t0)
        self.buffer.complete(span, ts0, self._ts(t) - ts0,
                             pid=self._pid_requests, tid=req.request_id,
                             cat="request", args=args)

    def on_submit(self, req) -> None:
        self.buffer.set_thread_name(self._pid_requests, req.request_id,
                                    f"req {req.request_id}")
        self._req_begin(req, "queued", req.metrics.arrival)

    def on_admit(self, req, n_cached: int = 0,
                 cache_lookup: bool = False) -> None:
        t = req.metrics.admitted
        self._req_end(req, "queued", t)
        self._req_begin(req, "prefill", t)
        self._req_cached[req.request_id] = \
            self._req_cached.get(req.request_id, 0) + n_cached
        if cache_lookup:
            lb = self._labels()
            self.c_cache_lookups.inc(**lb)
            if n_cached > 0:
                self.c_cache_hits.inc(**lb)
                self.c_cache_hit_tokens.inc(n_cached, **lb)
            self.instant("prefix_cache",
                         request=req.request_id,
                         hit=n_cached > 0, cached_tokens=n_cached)

    def on_chunk(self, req, cursor: int, take: int) -> None:
        self.buffer.instant("chunk", self._ts(), pid=self._pid_requests,
                            tid=req.request_id, cat="request",
                            args={"cursor": cursor, "take": take})

    def on_decode_kv(self, walked: int, grid: int) -> None:
        """Count a decode step's paged-attention KV steps, from the
        positions mirror (``PagedKVPool.decode_kv_steps``)."""
        lb = self._labels()
        self.c_kv_steps.inc(walked, kind="live", **lb)
        self.c_kv_steps.inc(grid - walked, kind="skipped", **lb)

    def on_prefill_complete(self, req) -> None:
        t = self.clock()
        self._req_end(req, "prefill", t,
                      args={"chunks": req.metrics.prefill_chunks,
                            "cached_tokens":
                                self._req_cached.get(req.request_id, 0)})
        self._req_begin(req, "decode", t)

    def on_preempt(self, req, reason: str) -> None:
        t = self.clock()
        self._req_end(req, "prefill", t)
        self._req_end(req, "decode", t)
        self.c_preempted.inc(reason=reason, **self._labels())
        self.buffer.instant("preempted", self._ts(t),
                            pid=self._pid_requests, tid=req.request_id,
                            cat="request", args={"reason": reason})
        self.instant("preempt", victim=req.request_id, reason=reason,
                     tokens_kept=len(req.tokens))
        self._req_begin(req, "queued", t)

    def _summary(self, req) -> None:
        m = req.metrics
        self.buffer.instant(
            "request_summary", self._ts(m.finished),
            pid=self._pid_requests, tid=req.request_id, cat="lifecycle",
            args={"id": req.request_id, "family": m.family,
                  "status": req.status.value, "admitted": m.admitted,
                  "first_token": m.first_token, "finished": m.finished,
                  "n_tokens": m.n_tokens,
                  "prefill_chunks": m.prefill_chunks,
                  "n_preemptions": m.n_preemptions,
                  "last_preempt_reason": m.last_preempt_reason,
                  "cached_tokens":
                      self._req_cached.pop(req.request_id, 0)})
        self._req_open.pop(req.request_id, None)

    def on_finish(self, req) -> None:
        t = req.metrics.finished
        self.c_decoded.inc(req.metrics.n_tokens, **self._labels())
        self.c_finished.inc(status=req.status.value, **self._labels())
        self._req_end(req, "decode", t,
                      args={"n_tokens": req.metrics.n_tokens})
        self._summary(req)

    def on_evict(self, req) -> None:
        t = req.metrics.finished
        self.c_finished.inc(status=req.status.value, **self._labels())
        self._req_end(req, "queued", t)
        self.buffer.instant("evicted", self._ts(t), pid=self._pid_requests,
                            tid=req.request_id, cat="request",
                            args={"reason": "queue_timeout"})
        self._summary(req)

    def on_withdraw(self, req) -> None:
        """A fleet router pulled this queued request out of the engine
        (work-steal or preemption drain); the destination engine's tracer
        re-opens "queued" via its own ``on_submit``, so the request's
        thread shows one queued span per engine it visited."""
        t = self.clock()
        self._req_end(req, "queued", t, args={"withdrawn": True})
        self.buffer.instant("withdrawn", self._ts(t),
                            pid=self._pid_requests, tid=req.request_id,
                            cat="request")

    # -------------------------------------------------------------- export
    def counters_text(self) -> str:
        return self.registry.prometheus_text()

    def write_trace(self, path: str) -> None:
        self.buffer.write(path)


class NullRouterTracer:
    """Disabled fleet-router tracer, mirroring ``NullTracer``: ``enabled``
    is False, every hook no-ops, and the router guards call sites on the
    flag so an untraced fleet does zero observability work per route."""
    enabled = False

    def attach(self, fleet, name=""):
        return self

    def on_route(self, req_id, decision):
        pass

    def on_reroute(self, req_id, kind, src, dst):
        pass

    def on_imbalance(self, spread):
        pass


NULL_ROUTER_TRACER = NullRouterTracer()

_ROUTER_TID = 0


class RouterTracer:
    """Fleet-router observability, sharing the replica tracers' buffer and
    registry so one trace file shows the router's decisions interleaved
    with every replica's step/request tracks.

    The router gets its own Perfetto process (pid allocation composes
    with ``ServingTracer.attach``'s pair scheme: pids are derived from
    the buffer's named-process count, which only grows, so tracks never
    collide).  Per routing decision it emits a "route" instant carrying
    the chosen replica, the policy, which score component won, the
    matched-prefix fraction, and the loser loads — enough to replay any
    routing decision from the trace alone.  Rebalance actions ("steal",
    "drain") get their own instants, and counters land in the shared
    registry: ``fleet_routing_decisions_total{policy,picked_by}``,
    ``fleet_reroutes_total{kind}``, ``fleet_route_prefix_tokens_total``,
    and a ``fleet_queue_imbalance`` gauge (max - min replica queue
    depth, sampled every rebalance check).
    """

    enabled = True

    def __init__(self, *, buffer: TraceBuffer | None = None,
                 registry: MetricsRegistry | None = None,
                 clock=None, name: str = "router"):
        self.buffer = buffer if buffer is not None else TraceBuffer()
        self.registry = registry if registry is not None else MetricsRegistry()
        self.clock = clock
        self.name = name
        self.t0: float | None = None
        self._pid: int | None = None

    def attach(self, fleet, name: str = "") -> "RouterTracer":
        if self.clock is None:
            self.clock = getattr(fleet, "_clock", time.monotonic)
        if self.t0 is None:
            self.t0 = self.clock()
        self.name = self.name or name or "router"
        base = len(self.buffer._named_processes)
        self._pid = 2 * base + 1
        self.buffer.set_process_name(self._pid, f"fleet {self.name}")
        self.buffer.set_thread_name(self._pid, _ROUTER_TID, "routing")
        r = self.registry
        self.c_decisions = r.counter(
            "fleet_routing_decisions_total",
            "routing decisions, by policy and winning score component")
        self.c_reroutes = r.counter(
            "fleet_reroutes_total",
            "queued requests moved between replicas, by mechanism "
            "(steal = imbalance rebalance, drain = preemption re-admit)")
        self.c_prefix_tokens = r.counter(
            "fleet_route_prefix_tokens_total",
            "prompt tokens already cached on the replica each request "
            "was routed to (routing-time estimate, not admission truth)")
        self.g_imbalance = r.gauge(
            "fleet_queue_imbalance",
            "max - min replica queue depth at the last rebalance check")
        return self

    def _ts(self, t: float | None = None) -> float:
        if self.t0 is None:
            self.t0 = self.clock() if self.clock else 0.0
        t = self.clock() if t is None else t
        return (t - self.t0) * 1e6

    def on_route(self, req_id: int, decision) -> None:
        self.c_decisions.inc(policy=decision.policy,
                             picked_by=decision.picked_by, fleet=self.name)
        if decision.prefix_tokens > 0:
            self.c_prefix_tokens.inc(decision.prefix_tokens, fleet=self.name)
        self.buffer.instant(
            "route", self._ts(), pid=self._pid, tid=_ROUTER_TID,
            cat="routing",
            args={"request": req_id, "replica": decision.replica,
                  "policy": decision.policy,
                  "picked_by": decision.picked_by,
                  "prefix_frac": round(decision.prefix_frac, 4),
                  "loads": [round(l, 4) for l in decision.loads]})

    def on_reroute(self, req_id: int, kind: str, src: int, dst: int) -> None:
        self.c_reroutes.inc(kind=kind, fleet=self.name)
        self.buffer.instant(
            kind, self._ts(), pid=self._pid, tid=_ROUTER_TID, cat="routing",
            args={"request": req_id, "src": src, "dst": dst})

    def on_imbalance(self, spread: int) -> None:
        self.g_imbalance.set(spread, fleet=self.name)
