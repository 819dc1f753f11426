"""Continuous-batching serving engine over the model zoo's compressed-weight
path.

The engine owns a preallocated KV pool and runs a SINGLE token-budgeted
iteration: every ``step()`` evicts expired queue entries, then assembles a
mixed batch of work under one ``token_budget`` of prefill tokens — in-flight
partial prefills advance first, then new admissions from the queue head —
and finally advances every prefill-complete request by one token in a single
fused decode step.  Long prompts no longer monopolize a step: a prompt
larger than the budget is split into chunks that land across consecutive
steps (per-request ``prefill_cursor``), each chunk attending to all KV the
request has already written.

ONE attention path: every piece of model work — one-shot prefill, prefill
chunk, fused decode — is ``models/transformer.unified_step`` over a pool
view (``attend_over_pool``).  The step function scatters its fresh KV into
the KV arena (slot rows or paged blocks) and attends IN PLACE against the
arena with the per-request cursor as a length mask; RoPE positions and the
causal/sliding-window mask are offset by the cursor, so chunked prefill is
numerically the one-shot prefill it replaces.  Nothing ever gathers a copy
of the already-written prefix, so each chunk's HBM traffic is independent
of the cursor — prefilling a P-token prompt costs O(P) arena traffic
total, not the O(P^2/budget) the old gather-per-chunk path paid.  Two
jitted functions cover everything: ``_step_fn`` (chunk-or-prefill,
retraces once per (batch, bucket) shape) and ``_decode_fn`` (fused decode,
compiles once).  Decoding requests keep emitting a token every step while
a long prompt trickles in beside them — bounded decode-tail inter-token
latency under mixed workloads, the regime where the paper's 8:16+outlier
compressed weights are deployed.  New requests join the running batch
without disturbing it — per-row attention/norms are independent and each
lane carries its own cursor, so a request's tokens are identical whether
it runs alone, packed next to strangers, or chunked under any budget
(tested).

Two KV layouts behind one API (``kv_layout=``):

  "slot"   SlotKVPool: contiguous [L, n_slots, max_len, KV, hd] arenas,
           one slot reserved per request for its lifetime.  Simplest and
           compile-once, but reserves max_len tokens of HBM per slot.
           The step functions address lanes through a ``SlotPoolView``
           (lane->slot rows + cursors).
  "paged"  PagedKVPool (serving/paged/): KV lives in block_size-token
           blocks allocated on demand from a shared arena, addressed
           through per-request block tables (``PagedPoolView``) and
           attended via the chunk-capable paged-attention kernel
           (serving/paged/paged_attention.py — jnp reference off-TPU,
           Pallas online-softmax over block tables on TPU, walking
           query tiles for long chunks).  Block allocation is
           chunk-aware — a half-prefilled prompt holds only the blocks
           its cursor has filled.  Identical prefixes share blocks
           read-only (prefix cache); decode or prefill pressure preempts
           the youngest request back to the queue, whose fully-written
           blocks are first published to the prefix cache so the resume
           restarts its cursor at the last fully-written block instead of
           recomputing everything.

Works unchanged for dense weights or ``SparseWeight`` compressed params
(models/sparse_serving.py): the weights are just a pytree passed through the
jitted step functions, so the 8:16 (+structured outlier) serving path gets
continuous batching and chunked prefill for free.

Supported families: every family in the model zoo, through one family
adapter layer (serving/families.py).  The engine owns scheduling — queue,
token budget, chunk planning, slot lifecycle, sampling — against one
primary pool; the adapter owns what a family actually keeps per request
(KV arenas, recurrent-state slots, encoder context rows) and the jitted
step functions over its ``unified_step``:

  dense/moe  Slot/Paged KV pool (this module's original path, verbatim)
  ssm        RecurrentStatePool only — O(1) state per request, no KV, so
             the chunk quantum widens to the whole token budget (no block
             math, no shape ladder worth bounding) and preemption swaps
             the state out and back (recompute would change float
             summation order)
  hybrid     shared-attention KV pool + mamba state slots under one slot
             identity, slot or paged (paged disables the prefix cache:
             cached KV blocks cannot reconstruct SSM state)
  encdec     decoder KV slots + read-only encoder context rows; the
             encoder runs once at admission at the TRUE input length
             (``submit(embeds=...)``)

Chunk batching: chunks at the same cursor are padded to power-of-two length
buckets and grouped, so the number of distinct compiled step shapes stays
small under mixed prompt lengths.  With the in-place causal mask the bucket
padding (after each chunk) cannot influence real logits or KV — pad lanes'
writes are dropped (slot) or routed to the trash block (paged), and pad
query outputs are never read — including MoE, whose local routing is
capacity-free (models/moe.py _moe_local).  The engine's traced functions
run under ``policy.suspended()`` precisely to keep that path on every mesh:
an active activation-sharding policy would flip MoE to the
capacity-BOUNDED expert-parallel route, where pad tokens compete with real
tokens for expert capacity.

Mesh-native serving (``mesh=``): pass a ``("data", "model")`` mesh and the
engine becomes tensor-parallel end to end through one placement layer
(serving/placement.py): params — dense and SparseWeight compressed buffers
alike — are committed out-dim-sharded over "model", both KV layouts shard
their arenas' KV-head dim, and both jitted step functions carry the
explicit in/out shardings of ``placement.step_fn_shardings`` (donated
arenas stay in place shard-for-shard).  Block tables, the prefix cache,
and all scheduling state stay host-side and layout-agnostic.  Token
streams are identical to the single-device engine
(tests/test_mesh_serving.py, tests/test_chunked_prefill.py); with no mesh
(default) nothing changes from the single-device behavior.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..models import sparse_serving
from . import families
from .cache_pool import CachePoolError
from .observe import NULL_TRACER, span
from .paged import OutOfBlocks
from .placement import ServingPlacement
from .request import Request, SamplingParams, Status
from .sampling import sample_tokens_logprobs, verify_draft
from .scheduler import (CHUNK_QUANTUM, PREEMPT_DECODE_PRESSURE,
                        PREEMPT_PREFILL_PRESSURE, QueueFull, RequestQueue,
                        pick_preemption_victim, plan_chunks,
                        resolve_token_budget, spec_verify_reserve)
from .speculative import SpeculativeConfig, Speculator, verify_bucket

SUPPORTED_FAMILIES = ("dense", "moe", "ssm", "hybrid", "encdec")
KV_LAYOUTS = ("slot", "paged")


def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class ServingEngine:
    def __init__(self, cfg, params, *, n_slots: int = 8, max_len: int = 256,
                 max_queue: int = 64, queue_timeout_s: float | None = None,
                 token_budget: int | None = None,
                 max_prefill_per_step: int | None = None,
                 kv_layout: str = "slot", kv_dtype: str = "bf16",
                 block_size: int = 16, n_blocks: int | None = None,
                 prefix_caching: bool = True, lookahead_blocks: int = 1,
                 paged_attn_backend: str | None = None, mesh=None,
                 max_ctx: int | None = None, clock=time.monotonic,
                 tracer=None, draft: SpeculativeConfig | None = None):
        if cfg.family not in SUPPORTED_FAMILIES:
            raise ValueError(
                f"ServingEngine supports {SUPPORTED_FAMILIES} families, not "
                f"{cfg.family!r}")
        if kv_layout not in KV_LAYOUTS:
            raise ValueError(f"kv_layout must be one of {KV_LAYOUTS}, "
                             f"not {kv_layout!r}")
        self.cfg = cfg
        self.placement = ServingPlacement(mesh, cfg)
        # one sharding-tree walk serves both the initial device_put and the
        # adapter's jitted functions' explicit in_shardings
        psh = self.placement.param_shardings(params)
        self.params = params if psh is None else jax.device_put(params, psh)
        # the family adapter owns the state substrate (pools + arenas) and
        # the jitted step functions; the engine schedules against its
        # primary pool.  ssm coerces the layout to "slot" (it has no KV to
        # page); encdec rejects "paged"
        if cfg.family == "ssm":
            kv_layout = "slot"
        self.adapter = families.build_adapter(
            cfg, self.params, self.placement, psh, kv_layout=kv_layout,
            n_slots=n_slots, max_len=max_len, block_size=block_size,
            n_blocks=n_blocks, prefix_caching=prefix_caching,
            paged_attn_backend=paged_attn_backend, max_ctx=max_ctx,
            kv_dtype=kv_dtype)
        self.kv_layout = kv_layout
        self.kv_dtype = kv_dtype
        self.pool = self.adapter.pool
        # projection applications per step-program call, by the path the
        # sparse linear takes (read by the tracer's counter)
        self.sparse_paths = sparse_serving.projection_paths(
            self.params, self.placement.active)
        # kept for introspection and the compiled-cost tests
        self._step_fn = self.adapter._step_fn
        self._decode_fn = self.adapter._decode_fn
        self.queue = RequestQueue(max_queue, queue_timeout_s)
        # per-step prefill token budget (max_prefill_per_step is the
        # deprecated request-count knob, aliased with a one-time warning).
        # resolve -> validate_token_budget raises a construction-time
        # ValueError when the budget cannot cover the chunk quantum or the
        # longest admissible prompt's first chunk — instead of a deep
        # stall inside scheduler.plan_chunks.  Pure-recurrent requests
        # carry O(1) state: no block math and no shape ladder worth
        # bounding, so the quantum floor check is waived (quantum=1) and
        # the effective planning quantum widens to the whole budget
        self.token_budget = resolve_token_budget(
            token_budget, max_prefill_per_step, max_len,
            quantum=1 if cfg.family == "ssm" else CHUNK_QUANTUM)
        self.chunk_quantum = (self.token_budget if cfg.family == "ssm"
                              else CHUNK_QUANTUM)
        self.lookahead_blocks = lookahead_blocks
        # speculative decoding (serving/speculative.py): a draft proposer
        # shares slot identity with the target; each decode step drafts k
        # tokens per request and verifies all k+1 candidate positions in
        # ONE chunk-shaped step (n_new = k+1 per lane) — same jitted fn,
        # same bucket ladder, so speculation adds no new compiled shapes
        # beyond the S buckets it actually uses
        self.spec = None
        if draft is not None:
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    "speculative decoding needs a KV-transformer target "
                    f"(dense/moe), not family {cfg.family!r}: rollback "
                    "relies on the cursor hiding rejected positions, which "
                    "recurrent state cannot do")
            self.spec = Speculator(draft, cfg, self.placement,
                                   n_slots=n_slots, max_len=max_len,
                                   kv_dtype=kv_dtype)
        self.n_spec_steps = 0
        self.n_drafted = 0
        self.n_accepted = 0
        self.running: dict[int, Request] = {}        # slot/row -> request
        self.finished: list[Request] = []
        self._clock = clock
        # observability: NULL_TRACER is a no-op singleton and every hot-path
        # call site is guarded by ``tracer.enabled``; the phase spans are
        # profiler annotations that record only while a profile is taken
        # (serving/observe.py)
        self.tracer = NULL_TRACER if tracer is None else tracer
        if self.tracer.enabled:
            self.tracer.attach(self)
        self._next_id = 0
        self.n_steps = 0
        self.n_preemptions = 0
        self.max_running = 0

        # per-slot sampling state (host side, fixed shapes)
        self._temps = np.zeros((n_slots,), np.float32)
        self._topks = np.zeros((n_slots,), np.int32)
        self._seeds = np.zeros((n_slots,), np.int32)
        self._gen_count = np.zeros((n_slots,), np.int32)
        self._last_token = np.zeros((n_slots,), np.int32)
        # logits of each slot's most recent position (a final prefill chunk
        # scatters here so first-token sampling reuses the one slot-wide
        # sampler)
        self._slot_logits = self.placement.place_replicated(
            jnp.zeros((n_slots, cfg.vocab), jnp.float32))

    # ------------------------------------------------------------ admission
    def submit(self, prompt, sampling: SamplingParams | None = None,
               on_token=None, on_finish=None, embeds=None,
               request_id: int | None = None) -> Request:
        """Enqueue a request; raises QueueFull when admission control
        rejects (queue at capacity) and ValueError when the request can
        never fit the pool.  ``embeds`` is the enc-dec family's encoder
        input ([S_enc, d] frontend features, run once at admission); other
        families reject it.  ``request_id`` lets a fleet router issue
        globally-unique ids across replicas; omitted, the engine numbers
        requests itself."""
        sampling = sampling or SamplingParams()
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if sampling.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        capacity = self.pool.max_request_tokens
        if len(prompt) + sampling.max_new_tokens > capacity:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({sampling.max_new_tokens}) exceeds KV capacity "
                f"{capacity}")
        self.adapter.validate_submit(prompt, sampling, embeds)
        rid = self._next_id if request_id is None else int(request_id)
        req = Request(rid, prompt, sampling,
                      on_token=on_token, on_finish=on_finish, embeds=embeds)
        req.metrics.family = self.cfg.family
        self._next_id = max(self._next_id + 1, rid + 1)
        req.metrics.arrival = self._clock()
        if not self.queue.try_push(req):
            raise QueueFull(f"queue at capacity ({self.queue.max_size})")
        if self.tracer.enabled:
            self.tracer.on_submit(req)
        return req

    def ingest(self, req: Request) -> None:
        """Adopt an already-constructed request from another engine (fleet
        work-stealing / preemption drain).  The request must be queued and
        unscheduled — it holds no slot, no KV, no per-engine state — so
        migrating it is just re-enqueueing: its sampling stream is keyed
        by (seed, tokens generated), which makes the token stream
        engine-agnostic.  Metrics (arrival time, preemption count) ride
        along untouched."""
        if req.status is not Status.QUEUED or req.slot is not None:
            raise ValueError(
                f"ingest needs a queued, unscheduled request, got "
                f"{req.status} (slot={req.slot})")
        capacity = self.pool.max_request_tokens
        need = len(self._seq(req)) + req.sampling.max_new_tokens \
            - len(req.tokens)
        if need > capacity:
            raise ValueError(
                f"request {req.request_id} needs {need} tokens, over this "
                f"engine's KV capacity {capacity}")
        if not self.queue.try_push(req):
            raise QueueFull(f"queue at capacity ({self.queue.max_size})")
        self._next_id = max(self._next_id, req.request_id + 1)
        if self.tracer.enabled:
            self.tracer.on_submit(req)

    def withdraw(self, req: Request) -> bool:
        """Remove a queued request from this engine so a fleet router can
        ``ingest`` it elsewhere.  Returns False when the request is no
        longer in this engine's queue (admitted or evicted since the
        router looked)."""
        if not self.queue.remove(req):
            return False
        if self.tracer.enabled:
            self.tracer.on_withdraw(req)
        return True

    def steal_youngest(self) -> Request | None:
        """Withdraw the YOUNGEST queued request (fleet work-stealing) —
        the tail of the FIFO queue, so the head-of-line request and
        everything the scheduler has promised service order to stays
        put.  None when the queue is empty."""
        req = self.queue.pop_back()
        if req is not None and self.tracer.enabled:
            self.tracer.on_withdraw(req)
        return req

    def prefix_match_length(self, prompt) -> int:
        """How many leading tokens of ``prompt`` this engine's prefix
        cache already holds — a side-effect-free host-side probe (no
        refcounts, no LRU touch; see ``PrefixCache.match_length``).
        Returns 0 for layouts/configs without a prefix cache, so routers
        can score any engine uniformly."""
        fn = getattr(self.pool, "prefix_match_length", None)
        if fn is None:
            return 0
        return fn([int(t) for t in np.asarray(prompt).reshape(-1)])

    # ------------------------------------------------------------ stepping
    @property
    def has_work(self) -> bool:
        return bool(self.running) or len(self.queue) > 0

    def step(self) -> dict:
        """One token-budgeted iteration: evict -> prefill chunks under the
        budget (in-flight cursors first, then admissions) -> fused decode
        of every prefill-complete request."""
        tr = self.tracer
        with span("serve.step", tr):
            now = self._clock()
            stats = {"evicted": 0, "admitted": 0, "finished": 0,
                     "decoded": 0, "preempted": 0, "prefill_tokens": 0,
                     "prefill_chunks": 0}
            with span("serve.schedule", tr):
                for req in self.queue.evict_expired(now):
                    req._finish(Status.EVICTED, now)
                    self.finished.append(req)
                    stats["evicted"] += 1
                    if tr.enabled:
                        tr.on_evict(req)

                # speculative decoding charges each decoding request's k+1
                # verify tokens against the step's token budget before
                # prefill planning (scheduler.spec_verify_reserve) — the
                # fused verify runs through the same chunk pipeline prefill
                # does, so the budget stays an honest bound on the step's
                # total token work
                budget = self.token_budget
                if self.spec is not None:
                    reserve = spec_verify_reserve(self.running,
                                                  self.spec.cfg.k)
                    budget = max(budget - reserve, 0)
                    stats["spec_reserved"] = min(reserve, self.token_budget)
                groups = self._plan_prefill(stats, now, budget)

            self._run_chunks(groups, stats)

            self.max_running = max(self.max_running, len(self.running))
            if any(r.status is Status.RUNNING
                   for r in self.running.values()):
                stats["finished"] += self._decode_once(stats)

            self.n_steps += 1
            if tr.enabled:
                tr.end_step(self, stats)
        return stats

    def run(self, max_steps: int | None = None) -> list[Request]:
        """Step until queue and slots drain; returns finished requests."""
        steps = 0
        while self.has_work and (max_steps is None or steps < max_steps):
            self.step()
            steps += 1
        return self.finished

    def stats(self) -> dict:
        """Engine-level counters plus the pool's memory/prefix accounting."""
        out = {"n_steps": self.n_steps, "max_running": self.max_running,
               "n_preemptions": self.n_preemptions,
               "n_running": len(self.running),
               "queue_depth": len(self.queue),
               "n_finished": len(self.finished),
               "family": self.cfg.family,
               "kv_layout": self.kv_layout,
               "kv_dtype": self.kv_dtype,
               "token_budget": self.token_budget,
               "placement": self.placement.describe()}
        pool_stats = getattr(self.pool, "stats", None)
        if pool_stats is not None:
            out["pool"] = pool_stats()
        if self.spec is not None:
            out["speculative"] = {
                "method": self.spec.cfg.method,
                "k": self.spec.cfg.k,
                "n_spec_steps": self.n_spec_steps,
                "drafted": self.n_drafted,
                "accepted": self.n_accepted,
                "acceptance_rate": (self.n_accepted / self.n_drafted
                                    if self.n_drafted else 0.0),
                # >1 means speculation is beating sequential decode: each
                # verify step emits accepted/steps drafts plus its
                # correction/bonus token
                "accepted_per_step": (self.n_accepted / self.n_spec_steps
                                      if self.n_spec_steps else 0.0)}
        return out

    def reset_stats(self) -> None:
        """Zero the step/preemption/concurrency/prefix counters (cached KV
        and compiled functions are kept) — benchmarks call this between a
        warm-up pass and the measured window."""
        self.n_steps = 0
        self.n_preemptions = 0
        self.max_running = 0
        self.n_spec_steps = 0
        self.n_drafted = 0
        self.n_accepted = 0
        if self.kv_layout == "paged":
            self.pool.reset_stats()

    # ------------------------------------------------------------ internals
    @staticmethod
    def _seq(req: Request) -> list[int]:
        """The token sequence prefill must cover: the prompt plus anything
        already generated before a preemption."""
        return list(req.prompt) + req.tokens

    def _written_seq(self, req: Request) -> list[int]:
        """The leading tokens whose KV the request has actually written —
        what a preemption can publish to the prefix cache for cursor
        resume.  Mid-prefill that is the cursor; for a decoding request
        everything but the last generated token (whose KV is only written
        when it is fed into the next decode step)."""
        seq = self._seq(req)
        if req.status is Status.PREFILLING:
            return seq[:req.prefill_cursor]
        return seq[:-1] if req.tokens else seq

    # -------------------------------------------------------- prefill phase
    def _plan_prefill(self, stats: dict, now: float,
                      budget: int) -> list[tuple[tuple[int, int], list]]:
        """Plan up to ``budget`` prompt tokens: advance in-flight prefill
        cursors first (admission order), then admit new requests from the
        queue head, FIFO, with layout-aware placement.  Returns the chunk
        groups, ((cursor, bucket), [(req, take)]), in the order they run."""
        tr = self.tracer
        in_flight = sorted(
            (r for r in self.running.values()
             if r.status is Status.PREFILLING),
            key=lambda r: (r.metrics.admitted, r.request_id))
        flight = [(r, len(self._seq(r)) - r.prefill_cursor)
                  for r in in_flight]
        queued = [(r, len(self._seq(r))) for r in self.queue]

        def try_admit(req, chunk):
            seq = self._seq(req)
            cache_lookup = False
            if self.kv_layout == "paged":
                if not self.pool.can_admit(chunk, self.lookahead_blocks):
                    return None
                cache_lookup = self.pool.prefix_cache is not None
                try:
                    row, n_cached = self.pool.admit(seq, alloc_tokens=0)
                except OutOfBlocks:
                    return None
                end = n_cached + min(chunk, len(seq) - n_cached)
                try:
                    self.pool.ensure_capacity(row, end)
                except OutOfBlocks:
                    self.pool.release(row)
                    return None
            else:
                row = self.pool.alloc()
                if row is None:
                    return None
                n_cached = 0
            popped = self.queue.pop()          # the planned head, by FIFO
            if popped is not req:
                raise CachePoolError("queue head changed during planning")
            self._install_running(req, row, now)
            if tr.enabled:
                tr.on_admit(req, n_cached, cache_lookup)
            # family admission work: swap-restore (stateful slot layouts
            # resume with their saved state/KV/context and cursor), or the
            # enc-dec encoder run — may raise past n_cached
            n_cached = max(n_cached, self.adapter.on_admit(req, row))
            req.prefill_cursor = n_cached
            stats["admitted"] += 1
            return len(seq) - n_cached

        chunk_plan = plan_chunks(flight, queued, budget,
                                 self.chunk_quantum, try_admit)

        runnable = []
        for req, take in chunk_plan:
            if self.running.get(req.slot) is not req:
                continue                       # preempted by a prior chunk
            if (self.kv_layout == "paged"
                    and not self._ensure_chunk_capacity(req, take, stats)):
                continue
            runnable.append((req, take))

        by_shape: dict[tuple[int, int], list] = {}
        for req, take in runnable:
            by_shape.setdefault((req.prefill_cursor, _bucket(take)),
                                []).append((req, take))
        return sorted(by_shape.items())

    def _run_chunks(self, groups: list, stats: dict) -> None:
        """Run the planned chunk groups, each one batched step, and emit a
        first token for every row whose prompt they completed."""
        for (cursor, bucket), group in groups:
            # a LATER plan entry's capacity loop may have preempted a
            # request after it was validated into runnable (its slot is
            # None and its cursor reset) — re-check liveness per group
            group = [(r, t) for r, t in group
                     if self.running.get(r.slot) is r
                     and r.prefill_cursor == cursor]
            if group:
                with span("serve.chunk", self.tracer):
                    done = self._run_chunk_group(group, cursor, bucket,
                                                 stats)
                if done:
                    stats["finished"] += self._emit_tokens(done)

    def _ensure_chunk_capacity(self, req: Request, take: int,
                               stats: dict) -> bool:
        """Grow the row's block table to hold the next chunk.  Under
        pressure: if anything is decoding, skip the chunk this step (the
        decoders drain and free blocks); otherwise preempt the youngest
        OTHER request and retry — the oldest prefill always makes
        progress, so the engine cannot livelock on its own prefills."""
        while True:
            try:
                self.pool.ensure_capacity(req.slot,
                                          req.prefill_cursor + take)
                return True
            except OutOfBlocks:
                if any(r.status is Status.RUNNING
                       for r in self.running.values()):
                    return False
                others = {s: r for s, r in self.running.items() if r is not req}
                if not others:
                    # cannot happen for admissible requests (submit bounds
                    # prompt+gen by pool capacity), so this is an
                    # accounting bug, not workload pressure
                    raise CachePoolError(
                        "sole prefilling request cannot grow its KV")
                self._preempt_one(stats, exclude=req,
                                  reason=PREEMPT_PREFILL_PRESSURE)

    def _install_running(self, req: Request, slot: int, now: float) -> None:
        req.slot = slot
        req.status = Status.PREFILLING
        req.metrics.admitted = now
        self.running[slot] = req
        self._temps[slot] = req.sampling.temperature
        self._topks[slot] = req.sampling.top_k
        self._seeds[slot] = req.sampling.seed
        # resumed requests continue their sampling stream at token index
        # len(tokens); fresh requests start at 0
        self._gen_count[slot] = len(req.tokens)
        if self.spec is not None:
            if req.draft_k == 0:
                req.draft_k = self.spec.cfg.k
            # whatever the draft arena holds at this slot belongs to a
            # previous occupant; the drafter catches up lazily from 0
            self.spec.on_admit(slot)

    def _run_chunk_group(self, group: list[tuple], cursor: int, bucket: int,
                         stats: dict) -> list[int]:
        """Run one batched step for rows sharing (cursor, bucket): write
        tokens [cursor, cursor+take) straight into the arena and attend in
        place against the already-written context (``unified_step`` — at
        cursor 0 this IS the one-shot prefill), then gather the last
        logits of every row whose cursor reached its sequence end into
        the per-slot logits.  Returns those rows' slots."""
        n = len(group)
        B = _bucket(n, 1)                   # batch pad, power-of-two ladder
        rows = [req.slot for req, _ in group]
        seqs = [self._seq(req) for req, _ in group]
        takes = [take for _, take in group]
        tokens = np.zeros((B, bucket), np.int32)
        cur = np.zeros((B,), np.int32)
        n_new = np.zeros((B,), np.int32)
        for i, (seq, take) in enumerate(zip(seqs, takes)):
            tokens[i, :take] = seq[cursor:cursor + take]
            cur[i] = cursor
            n_new[i] = take
        if self.kv_layout == "paged":
            lanes = self.pool.lane_tables(rows, B)
        else:
            self.pool.chunk_end_check(cursor, takes)
            lanes = self.pool.lane_rows(rows, B)
        logits = self.adapter.step_chunk(
            rows, jnp.asarray(lanes), jnp.asarray(cur), jnp.asarray(n_new),
            jnp.asarray(tokens))
        if self.tracer.enabled:
            self.tracer.on_step_program(self.sparse_paths)
        self.pool.advance_prefill(rows, [cursor + t for t in takes])
        stats["prefill_tokens"] += sum(takes)
        stats["prefill_chunks"] += n

        tr = self.tracer
        done_idx, done_rows, done_last = [], [], []
        for i, ((req, take), seq) in enumerate(zip(group, seqs)):
            req.prefill_cursor = cursor + take
            req.metrics.prefill_chunks += 1
            if tr.enabled:
                tr.on_chunk(req, cursor, take)
            if req.prefill_cursor == len(seq):
                req.status = Status.RUNNING
                if self.kv_layout == "paged":
                    self.pool.register_prefix(req.slot, seq)
                if tr.enabled:
                    tr.on_prefill_complete(req)
                done_idx.append(i)
                done_rows.append(req.slot)
                done_last.append(take - 1)
        if done_rows:
            last_logits = logits[jnp.asarray(done_idx),
                                 jnp.asarray(done_last)]
            self._slot_logits = self._slot_logits.at[
                jnp.asarray(done_rows)].set(last_logits.astype(jnp.float32))
        return done_rows

    # -------------------------------------------------------------- decode
    def _preempt_one(self, stats: dict, exclude: Request | None = None,
                     reason: str = PREEMPT_DECODE_PRESSURE) -> None:
        """Push the youngest running request (never ``exclude``) back to
        the queue head and release its blocks — after publishing its
        fully-written blocks to the prefix cache, so the resume restarts
        its cursor at the last fully-written block instead of
        re-prefilling prompt + generated from scratch (when the cache has
        been evicted in the meantime, the chunked prefill recomputes —
        token streams are identical either way)."""
        candidates = ({s: r for s, r in self.running.items() if r is not exclude}
                      if exclude is not None else self.running)
        victim_slot = pick_preemption_victim(candidates)
        req = self.running.pop(victim_slot)
        # stateful slot-layout families swap their state out (recompute
        # would change float summation order); attention-only families
        # return None and recompute exactly
        req.swap = self.adapter.save_for_preempt(
            req, victim_slot, len(self._written_seq(req)))
        if self.kv_layout == "paged":
            self.pool.register_prefix(victim_slot, self._written_seq(req))
        self.pool.release(victim_slot)
        req.slot = None
        req.status = Status.QUEUED
        req.prefill_cursor = 0
        req.n_preempted += 1
        req.metrics.n_preemptions += 1
        req.metrics.last_preempt_reason = reason
        self.queue.push_front(req)
        self.n_preemptions += 1
        if self.kv_layout == "paged":
            self.pool.n_preemptions += 1
        stats["preempted"] += 1
        if self.tracer.enabled:
            self.tracer.on_preempt(req, reason)

    def _decode_rows(self) -> list[int]:
        return sorted(s for s, r in self.running.items()
                      if r.status is Status.RUNNING)

    def _decode_once(self, stats: dict | None = None) -> int:
        """Advance every prefill-complete request one token in a single
        fused step (``unified_step`` at S=1 over every lane).  Rows
        mid-prefill share the batch but are masked out of position
        updates and sampling (their lanes compute a discarded garbage
        token — see cache_pool/pool docstrings for why the stray write is
        harmless)."""
        stats = stats if stats is not None else {"preempted": 0}
        if self.spec is not None:
            return self._speculative_decode(stats)
        with span("serve.decode", self.tracer):
            active = self._decode_rows()
            if self.kv_layout == "paged":
                while True:
                    try:
                        self.pool.prepare_decode(active)
                        break
                    except OutOfBlocks:
                        if len(self.running) <= 1:
                            # cannot happen for admissible requests (submit
                            # bounds prompt+gen by pool capacity), so this
                            # is an accounting bug, not workload pressure
                            raise CachePoolError(
                                "sole running request cannot grow its KV")
                        self._preempt_one(stats,
                                          reason=PREEMPT_DECODE_PRESSURE)
                        active = self._decode_rows()
                if not active:
                    return 0
                if self.tracer.enabled:
                    self.tracer.on_decode_kv(*self.pool.decode_kv_steps(
                        self.cfg.n_heads, self.cfg.window))
            stats["decoded"] = len(active)
            tokens = jnp.asarray(self._last_token[:, None])
            logits = self.adapter.step_decode(tokens, active)
            if self.tracer.enabled:
                self.tracer.on_step_program(self.sparse_paths)
            self._slot_logits = logits[:, 0].astype(jnp.float32)
        n_finished = self._emit_tokens(active)
        advanced = np.zeros((self.pool.n_slots,), bool)
        advanced[[s for s in active if s in self.running]] = True
        self.pool.advance_decode(advanced)
        return n_finished

    def _speculative_decode(self, stats: dict) -> int:
        """Draft k tokens per decoding request, verify all k+1 candidate
        positions in ONE fused chunk-shaped step, emit the accepted prefix
        plus a correction/bonus token, and roll the cursor back over the
        rejected tail.

        The verify call is the engine's existing ``step_chunk`` with
        per-lane ``cursor = len(seq) - 1`` (the last emitted token's KV is
        written here, preserving the written-positions invariant) and
        ``n_new = n_draft + 1``; both the batch and S axes ride the
        ``_bucket`` ladders, so speculation compiles a handful of shapes
        total, never one per k.  Rollback is ``advance_prefill`` to
        ``cursor + accepted + 1``: the garbage KV beyond it is hidden by
        the cursor length mask (slot) or sits in blocks the row still owns
        (paged) until the next step overwrites it."""
        tr = self.tracer
        spec = self.spec
        active = self._decode_rows()
        seqs = {s: self._seq(self.running[s]) for s in active}
        cap = self.pool.max_request_tokens
        ks = []
        for s in active:
            req = self.running[s]
            # never draft past the request's finish line or the row's KV
            # capacity (the verify writes len(seq)-1 + k + 1 positions)
            k = min(req.draft_k,
                    req.sampling.max_new_tokens - len(req.tokens) - 1,
                    cap - len(seqs[s]))
            ks.append(max(k, 0))

        with span("serve.draft", tr):
            proposals = spec.propose(active, [seqs[s] for s in active], ks)
        drafts = dict(zip(active, proposals))

        with span("serve.decode", tr):
            if self.kv_layout == "paged":
                while True:
                    try:
                        self.pool.prepare_decode(
                            active, [len(drafts[s]) + 1 for s in active])
                        break
                    except OutOfBlocks:
                        if len(self.running) <= 1:
                            raise CachePoolError(
                                "sole running request cannot grow its KV")
                        self._preempt_one(stats,
                                          reason=PREEMPT_DECODE_PRESSURE)
                        active = self._decode_rows()
                if not active:
                    return 0

            n = len(active)
            nds = [len(drafts[s]) for s in active]
            # Verify always runs the full lane complement (like fused
            # decode): a constant B keeps the compiled-variant count linear
            # in the S ladder instead of B x S, so a trickle of arrivals
            # can't hit batch shapes the warmup never saw.
            B = _bucket(self.pool.n_slots, 1)
            S = verify_bucket(max(nds) + 1, self.spec.cfg.k)
            tokens = np.zeros((B, S), np.int32)
            cur = np.zeros((B,), np.int32)
            n_new = np.zeros((B,), np.int32)
            draft_arr = np.zeros((B, S), np.int32)
            n_draft = np.zeros((B,), np.int32)
            lane_slot = np.zeros((B,), np.int64)  # pad lanes borrow slot 0
            for i, s in enumerate(active):
                nd = nds[i]
                cur[i] = len(seqs[s]) - 1
                tokens[i, 0] = self._last_token[s]
                if nd:
                    tokens[i, 1:1 + nd] = drafts[s]
                    draft_arr[i, :nd] = drafts[s]
                n_new[i] = nd + 1
                n_draft[i] = nd
                lane_slot[i] = s
            if self.kv_layout == "paged":
                lanes = self.pool.lane_tables(active, B)
            else:
                lanes = self.pool.lane_rows(active, B)
            logits = self.adapter.step_chunk(
                active, jnp.asarray(lanes), jnp.asarray(cur),
                jnp.asarray(n_new), jnp.asarray(tokens))
            if tr.enabled:
                tr.on_step_program(self.sparse_paths)
            # leave-one-in verification over the whole (bucketed) batch —
            # pad lanes verify slot 0's parameters against garbage and are
            # never read, keeping verify_draft's compiled shapes on the same
            # ladder
            n_acc, v_toks, v_lps = verify_draft(
                logits.astype(jnp.float32), jnp.asarray(draft_arr),
                jnp.asarray(n_draft), jnp.asarray(self._temps[lane_slot]),
                jnp.asarray(self._topks[lane_slot]),
                jnp.asarray(self._seeds[lane_slot]),
                jnp.asarray(self._gen_count[lane_slot]))
        with span("serve.emit", tr):
            with span("serve.sync", tr):
                n_acc = np.asarray(n_acc)
                v_toks = np.asarray(v_toks)
                v_lps = np.asarray(v_lps)

            # cursor rollback/advance BEFORE emission releases any slot:
            # each row's written positions become exactly len(seq) - 1
            # again once its accepted+1 tokens are appended (the engine
            # invariant)
            self.pool.advance_prefill(
                active,
                [int(cur[i]) + 1 + int(n_acc[i]) for i in range(n)])

            now = self._clock()
            n_finished = 0
            drafted = accepted = emitted = 0
            for i, s in enumerate(active):
                req = self.running[s]
                nd, a = nds[i], int(n_acc[i])
                drafted += nd
                accepted += a
                req.metrics.spec_drafted += nd
                req.metrics.spec_accepted += a
                if spec.cfg.adaptive and nd > 0:
                    if a == nd:
                        req.draft_k = min(req.draft_k + 1, spec.cfg.max_k)
                    elif 2 * a < nd:
                        req.draft_k = max(req.draft_k - 1, spec.cfg.min_k)
                spec.rollback(s, nd, a)
                sp = req.sampling
                for j in range(a + 1):
                    tok = int(v_toks[i, j])
                    req._emit(tok, now, logprob=float(v_lps[i, j]))
                    self._last_token[s] = tok
                    self._gen_count[s] += 1
                    emitted += 1
                    if (len(req.tokens) >= sp.max_new_tokens
                            or (sp.eos_id is not None
                                and tok == sp.eos_id)):
                        req._finish(Status.FINISHED, now)
                        self.finished.append(req)
                        del self.running[s]
                        self.pool.release(s)
                        n_finished += 1
                        if tr.enabled:
                            tr.on_finish(req)
                        break
            self.n_spec_steps += 1
            self.n_drafted += drafted
            self.n_accepted += accepted
            stats["decoded"] = n
            stats["spec_drafted"] = drafted
            stats["spec_accepted"] = accepted
            stats["spec_emitted"] = emitted
        return n_finished

    def _emit_tokens(self, slots: list[int]) -> int:
        """Sample one token for ``slots`` from _slot_logits, stream it, and
        retire requests that hit max_new_tokens / EOS.  Returns retirements."""
        tr = self.tracer
        with span("serve.emit", tr):
            toks, lps = sample_tokens_logprobs(
                self._slot_logits, jnp.asarray(self._temps),
                jnp.asarray(self._topks), jnp.asarray(self._seeds),
                jnp.asarray(self._gen_count))
            with span("serve.sync", tr):
                toks, lps = np.asarray(toks), np.asarray(lps)
            now = self._clock()
            n_finished = 0
            for slot in slots:
                req = self.running[slot]
                tok = int(toks[slot])
                req._emit(tok, now, logprob=float(lps[slot]))
                self._last_token[slot] = tok
                self._gen_count[slot] += 1
                sp = req.sampling
                if (len(req.tokens) >= sp.max_new_tokens
                        or (sp.eos_id is not None and tok == sp.eos_id)):
                    req._finish(Status.FINISHED, now)
                    self.finished.append(req)
                    del self.running[slot]
                    self.pool.release(slot)
                    n_finished += 1
                    if tr.enabled:
                        tr.on_finish(req)
        return n_finished
