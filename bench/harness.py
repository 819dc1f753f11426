"""The run of one cell, from the seed to the window's records.

``build`` makes the weights from the seed and the engine as
``launch/serve.py`` would (paged KV, the family adapter's compiled chunk and
decode programs, the Pallas paged-attention kernel); ``warm`` runs every
program shape the cell's traffic can reach; ``drive`` offers the traffic
until the window closes and keeps what each request and step did on the
host clock.  Nothing here reads a number from the program but its tokens,
the ``decoded`` count that ``engine.step`` returns and its admission time
(``RequestMetrics.admitted``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np

from bench import lengths, weights

CLOCK = time.monotonic


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------

def model_config(conf: dict):
    """The program's ModelConfig for a configuration file's ``model``."""
    from repro.configs.base import ModelConfig
    m = dict(conf["model"])
    m["dtype"] = jnp.dtype(m["dtype"])
    return ModelConfig(name=conf["name"], source=conf["source"],
                       remat=False, **m)


def compressor(wfmt: dict):
    """``w -> served weight`` for the configuration's weight format, or
    None for dense weights."""
    if wfmt["format"] == "dense":
        return None
    from repro.core import SparsifyConfig
    from repro.models.sparse_serving import _to_sparse_weight
    scfg = SparsifyConfig(weight_pattern=wfmt["weight_pattern"],
                          outlier_pattern=wfmt["outlier_pattern"],
                          scorer=wfmt["scorer"], use_smoothquant=False,
                          use_variance_correction=wfmt["variance_correction"])
    return functools.partial(_to_sparse_weight, scfg=scfg,
                             quantize=wfmt["values"] == "int8")


def build(conf: dict, seed: int):
    """(engine, params) for one configuration, weights made from ``seed``."""
    from repro.serving import ServingEngine
    cfg = model_config(conf)
    params = weights.served_params(seed, conf["model"],
                                   compressor(conf["weights"]))
    jax.block_until_ready(params)
    dep = conf["deployment"]
    engine = ServingEngine(
        cfg, params, n_slots=dep["n_slots"], max_len=dep["max_len"],
        max_queue=dep["max_queue"], token_budget=dep["token_budget"],
        kv_layout=dep["kv_layout"], block_size=dep["block_size"],
        n_blocks=dep["n_blocks"], prefix_caching=dep["prefix_caching"],
        paged_attn_backend=dep.get("paged_attention"))
    return engine, params


# --------------------------------------------------------------------------
# the benchmark's own spans around the calls into each layer
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Call:
    kind: str                  # "chunk" | "decode"
    tokens: int                # real (unpadded) tokens
    contexts: list             # per real row: (cursor before, new tokens)
    heads: int                 # rows that emit a token from this call
    traced: bool               # logged while the profiler ran


class CallLog:
    """Per-instance wrappers of ``adapter.step_chunk`` and
    ``adapter.step_decode``: each call runs inside a ``bench.chunk`` /
    ``bench.decode`` profiler annotation and is logged with its rows and
    contexts.  The program itself is untouched."""

    def __init__(self, engine):
        self.calls: list[Call] = []
        self.annotate = False
        a = engine.adapter
        pool = engine.pool
        chunk, decode = a.step_chunk, a.step_decode

        def step_chunk(rows, lanes, cur, n_new, tokens):
            n = len(rows)
            cur_h = np.asarray(cur)[:n].tolist()
            new_h = np.asarray(n_new)[:n].tolist()
            heads = 0
            for slot, c, k in zip(rows, cur_h, new_h):
                req = engine.running.get(slot)
                heads += req is not None and c + k == len(req.prompt) + len(
                    req.tokens)
            with self._span("bench.chunk"):
                out = chunk(rows, lanes, cur, n_new, tokens)
            self.calls.append(Call("chunk", int(sum(new_h)),
                                   list(zip(cur_h, new_h)), heads,
                                   self.annotate))
            return out

        def step_decode(tokens, active):
            pos = pool._pos_np
            ctx = [(int(pos[s]), 1) for s in active]
            with self._span("bench.decode"):
                out = decode(tokens, active)
            self.calls.append(Call("decode", len(active), ctx, len(active),
                                   self.annotate))
            return out

        a.step_chunk = step_chunk
        a.step_decode = step_decode

    def _span(self, name):
        if not self.annotate:
            return nullcontext()
        return jax.profiler.TraceAnnotation(name)


# --------------------------------------------------------------------------
# warm-up
# --------------------------------------------------------------------------

def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def chunk_shapes(dep: dict, min_prompt: int) -> list[tuple[int, int]]:
    """Every (B, bucket) chunk program the engine can run for prompts of
    at least ``min_prompt`` tokens under its token budget.

    A chunk's bucket is the power of two (from 8) at or above its tokens;
    rows share a program only when they share cursor and bucket.  At most
    one chunk per step is cut short by the budget, so several rows meet
    only as whole prompts admitted together at cursor 0: then each has
    more than bucket / 2 tokens and at least ``min_prompt``, and their sum
    stays within the budget.  Below ``min_prompt`` a chunk is a remainder
    and runs alone."""
    budget, slots = dep["token_budget"], dep["n_slots"]
    top = _bucket(min(budget, dep["max_len"]), 8)
    out = []
    b = 8
    while b <= top:
        if b < min_prompt:
            n_max = 1
        else:
            n_max = min(slots, budget // max(b // 2 + 1, min_prompt))
        B = 1
        while B <= _bucket(max(n_max, 1), 1):
            out.append((B, b))
            B *= 2
        b *= 2
    return out


def warm(engine, shapes: list[tuple[int, int]]) -> None:
    """Run each chunk program shape, the decode program and the small
    eager ops around them once, with every write routed to the trash
    block, so that nothing the traffic reaches compiles later."""
    pool, a = engine.pool, engine.adapter
    V, n_slots = engine.cfg.vocab, pool.n_slots
    slot_logits = jnp.zeros((n_slots, V), jnp.float32)
    for B, S in shapes:
        lanes = pool.lane_tables([], B)
        zeros = jnp.zeros((B,), jnp.int32)
        logits = a.step_chunk([], jnp.asarray(lanes), zeros, zeros,
                              jnp.zeros((B, S), jnp.int32))
        # the engine's gather of finishing rows' logits and its scatter
        # into the per-slot logits, for every count of finishing rows
        for k in range(1, B + 1):
            idx = jnp.asarray(list(range(k)))
            last = logits[idx, jnp.asarray([S - 1] * k)]
            slot_logits = slot_logits.at[jnp.asarray(list(range(k)))].set(
                last.astype(jnp.float32))
    logits = a.step_decode(jnp.zeros((n_slots, 1), jnp.int32), [])
    slot_logits = logits[:, 0].astype(jnp.float32)
    from repro.serving.sampling import sample_tokens_logprobs
    zi = jnp.zeros((n_slots,), jnp.int32)
    toks, _ = sample_tokens_logprobs(slot_logits,
                                     jnp.zeros((n_slots,), jnp.float32),
                                     zi, zi, zi)
    np.asarray(toks)


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    spec: object
    due: float                 # host clock
    sent: float
    req: object = None
    times: list = dataclasses.field(default_factory=list)
    finished: float | None = None
    rejected: bool = False


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decoded: int
    prefill_tokens: int
    waiting: int               # requests queued after the step
    running: int               # requests holding a slot after the step


@dataclasses.dataclass
class Window:
    start: float
    end: float
    records: list
    steps: list
    compiles: dict             # compile events inside the window, by name


class CompileCounter:
    """Counts JAX's compile and compile-cache events while ``on``."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_hits",
             "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.on = False
        self.counts = {n: 0 for n in self.NAMES}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *args, **kw):
        if self.on and name in self.counts:
            self.counts[name] += 1


def drive(engine, source, *, vocab: int, seed: int, warmup_s: float,
          seconds: float, counter: CompileCounter, calls: CallLog,
          trace=None) -> Window:
    """Offer ``source``'s traffic from now: ``warmup_s`` to reach steady
    state, then the window of ``seconds``.  Returns when the window
    closes.  ``trace`` = (start offset in the window, seconds, directory)
    profiles that stretch with the benchmark's annotations on."""
    from repro.serving import SamplingParams
    from repro.serving.scheduler import QueueFull

    t0 = CLOCK()
    ws = t0 + warmup_s
    we = ws + seconds
    records: list[Record] = []
    steps: list[Step] = []
    tr_start = tr_end = None
    if trace is not None:
        tr_start, tr_end = ws + trace[0], ws + trace[0] + trace[1]
    tracing = traced = False

    def submit(spec):
        due = t0 + spec.due
        rec = Record(spec, due, CLOCK())
        prompt = lengths.prompt_tokens(seed, spec.index, spec.prompt_len,
                                       vocab)

        def on_token(req, tok, rec=rec):
            rec.times.append(CLOCK())

        def on_finish(req, rec=rec):
            rec.finished = CLOCK()
            source.on_finish(rec.spec, rec.finished - t0)
        try:
            rec.req = engine.submit(
                prompt, SamplingParams(max_new_tokens=spec.out_len),
                on_token=on_token, on_finish=on_finish)
        except (QueueFull, ValueError):
            rec.rejected = True
        records.append(rec)

    while True:
        now = CLOCK()
        if not counter.on and now >= ws:
            counter.on = True
        if now >= we:
            break
        if trace is not None:
            if not tracing and not traced and now >= tr_start:
                jax.profiler.start_trace(trace[2])
                calls.annotate = tracing = traced = True
            elif tracing and now >= tr_end:
                jax.profiler.stop_trace()
                calls.annotate = tracing = False
        with (jax.profiler.TraceAnnotation("bench.generator") if tracing
              else nullcontext()):
            for spec in source.pop_due(now - t0):
                submit(spec)
        if engine.has_work:
            a = CLOCK()
            with (jax.profiler.TraceAnnotation("bench.engine_step")
                  if tracing else nullcontext()):
                st = engine.step()
            steps.append(Step(a, CLOCK(), st["decoded"],
                              st["prefill_tokens"], len(engine.queue),
                              len(engine.running)))
        else:
            wait = min(t0 + source.next_due(), we) - CLOCK()
            if wait > 0:
                time.sleep(min(wait, 0.002))
    counter.on = False
    if tracing:
        jax.profiler.stop_trace()
        calls.annotate = False
    return Window(ws, we, records, steps, dict(counter.counts))
