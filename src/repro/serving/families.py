"""Family adapters: one per-request state substrate per model family,
behind one engine-facing surface.

The engine schedules requests; it does not know what a family keeps per
request.  Each adapter owns that answer — the primary pool the scheduler
allocates slots from, any auxiliary arenas, and the jitted step functions
built over the family's ``unified_step`` — behind five hooks:

  ``step_chunk(rows, lanes, cur, n_new, tokens)``
      run one (cursor, bucket) prefill-chunk group, adopt the donated
      output arenas, return the logits [B, S, V];
  ``step_decode(tokens, active)``
      run the fused S=1 decode over every lane, adopt, return
      logits [n_slots, 1, V];
  ``on_admit(req, slot) -> n_restored``
      per-request admission work: restore a swap-preempted request's
      state verbatim (returning how many tokens of its sequence are
      already absorbed, so the engine resumes the cursor there), or run
      the enc-dec encoder at the true input length;
  ``save_for_preempt(req, slot, n_written) -> blob | None``
      what preemption must save to keep the resumed token stream exactly
      the uninterrupted one.  None means "recompute is exact" (softmax
      attention: KV recomputed from tokens is the same numbers) — the
      stateful slot families return a swap blob instead, because a
      recurrent state recomputed under different chunk boundaries differs
      in float summation order;
  ``validate_submit(prompt, sampling, embeds)``
      family-specific admission checks (enc-dec requires encoder embeds
      and bounds them by the context arena).

Per family:

  dense/moe  ``TransformerAdapter`` — Slot/Paged KV pool, the engine's
             original two step functions, verbatim.
  ssm        ``RecurrentAdapter`` — ``RecurrentStatePool`` only: O(1)
             state per request, no KV.  kv_layout is coerced to "slot"
             (there is nothing to page).  Swap preemption.
  hybrid     ``HybridAdapter`` — a ``SlotKVPool``/``PagedKVPool`` sized
             to the shared-attention applications PLUS a
             ``RecurrentStatePool`` for the mamba layers, one slot
             identity across both (``HybridStatePool``), mixed in one
             jitted step via ``HybridPoolView``.  Slot layout swaps
             state+KV on preemption (exact); the paged layout recomputes
             from scratch with the prefix cache disabled — cached KV
             blocks cannot reconstruct SSM state, and the recompute may
             differ from the uninterrupted stream in the last ulp (the
             documented trade for paged memory).
  encdec     ``EncDecAdapter`` — decoder-side ``SlotKVPool`` plus a
             read-only ``EncoderContextPool``; admission runs the encoder
             at the request's TRUE input length (bidirectional encoders
             cannot pad) and installs the projected cross-attention rows.
             Swap preemption (KV rows + context + position).

Every traced function runs under ``policy.suspended()`` for the same
reason the engine's always have (capacity-free MoE routing under bucket
padding), and every family's decode shares float operation order with its
``decode_lockstep`` — the engine-vs-lockstep token-identity property
tests/test_family_engines.py asserts.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..models import transformer as tfm
from ..models import whisper as whi
from ..models import xlstm as xls
from ..models import zamba as zam
from ..parallel import policy as pol
from .cache_pool import CachePoolError, SlotKVPool, SlotPoolView
from .paged import PagedKVPool, PagedPoolView
from .state_pool import (EncDecPoolView, EncoderContextPool, HybridPoolView,
                         RecurrentStatePool, RecurrentStateView)


def _suspend(fn, partitioned=False):
    """Trace ``fn`` under a suspended activation-sharding policy (see the
    engine docstring: an ambient policy would flip MoE onto the
    capacity-bounded expert-parallel path where pad tokens evict real
    ones), marked partitioned for a program partitioned over a mesh
    (``policy.partitioned``: the sparse linear keeps its portable path
    there, ``sparse_serving.kernel_serves``).  Every step
    program lowers as ``jit_serve_step``: profiler traces and the benchmark
    find the programs by that name."""
    def serve_step(*args):
        with pol.suspended(partitioned):
            return fn(*args)
    return serve_step


def _jit(placement, fn, donate=(), in_shardings=None, out_shardings=None):
    """jit with explicit shardings on a mesh, a plain jit otherwise."""
    if not placement.active:
        return jax.jit(_suspend(fn), donate_argnums=donate)
    return jax.jit(_suspend(fn, partitioned=True), donate_argnums=donate,
                   in_shardings=in_shardings, out_shardings=out_shardings)


class FamilyAdapter:
    """Shared no-op hooks; subclasses override what their family needs."""
    cfg = None
    params = None
    pool = None
    kv_layout = "slot"

    def on_admit(self, req, slot: int) -> int:
        return 0

    def save_for_preempt(self, req, slot: int, n_written: int):
        return None

    def validate_submit(self, prompt, sampling, embeds) -> None:
        if embeds is not None:
            raise ValueError(
                f"family {self.cfg.family!r} takes token prompts only; "
                f"embeds= is for the enc-dec family")


# --------------------------------------------------------------------------
# dense / moe: the engine's original transformer path, verbatim
# --------------------------------------------------------------------------

class TransformerAdapter(FamilyAdapter):
    def __init__(self, cfg, params, placement, psh, *, kv_layout, n_slots,
                 max_len, block_size, n_blocks, prefix_caching,
                 paged_attn_backend, kv_dtype: str = "bf16"):
        self.cfg, self.params, self.kv_layout = cfg, params, kv_layout
        self.kv_dtype = kv_dtype
        quant = kv_dtype == "int8"
        self.quantized = quant
        if kv_layout == "paged":
            self.pool = PagedKVPool(cfg, n_slots, max_len,
                                    block_size=block_size, n_blocks=n_blocks,
                                    prefix_caching=prefix_caching,
                                    placement=placement, kv_dtype=kv_dtype)
        else:
            self.pool = SlotKVPool(cfg, n_slots, max_len, placement=placement,
                                   kv_dtype=kv_dtype)
        sh = placement.step_fn_shardings(psh, kv_layout, kv_dtype)
        # int8 arenas thread two scale tensors right after k/v through both
        # jitted steps, donated alongside (quantize-on-scatter updates them
        # in place); otherwise the signatures are the original ones
        if kv_layout == "paged":
            trash = self.pool.trash_block
            if quant:
                self._step_fn = _jit(
                    placement,
                    lambda p, k, v, ks, vs, bt, cur, nn, t: tfm.unified_step(
                        p, PagedPoolView(k, v, bt, cur, nn, trash, ks, vs),
                        {"tokens": t}, cfg, attn_backend=paged_attn_backend),
                    donate=(1, 2, 3, 4), **sh["step"])
                self._decode_fn = _jit(
                    placement,
                    lambda p, k, v, ks, vs, bt, pos, t: tfm.unified_step(
                        p, PagedPoolView(k, v, bt, pos, jnp.ones_like(pos),
                                         trash, ks, vs),
                        {"tokens": t}, cfg, attn_backend=paged_attn_backend),
                    donate=(1, 2, 3, 4), **sh["decode"])
            else:
                self._step_fn = _jit(
                    placement,
                    lambda p, k, v, bt, cur, nn, t: tfm.unified_step(
                        p, PagedPoolView(k, v, bt, cur, nn, trash),
                        {"tokens": t}, cfg, attn_backend=paged_attn_backend),
                    donate=(1, 2), **sh["step"])
                self._decode_fn = _jit(
                    placement,
                    lambda p, k, v, bt, pos, t: tfm.unified_step(
                        p, PagedPoolView(k, v, bt, pos, jnp.ones_like(pos),
                                         trash),
                        {"tokens": t}, cfg, attn_backend=paged_attn_backend),
                    donate=(1, 2), **sh["decode"])
        elif quant:
            self._step_fn = _jit(
                placement,
                lambda p, k, v, ks, vs, rows, cur, nn, t: tfm.unified_step(
                    p, SlotPoolView(k, v, rows, cur, nn, ks, vs),
                    {"tokens": t}, cfg),
                donate=(1, 2, 3, 4), **sh["step"])
            self._decode_fn = _jit(
                placement,
                lambda p, k, v, ks, vs, pos, t: tfm.unified_step(
                    p, SlotPoolView(k, v, None, pos, jnp.ones_like(pos),
                                    ks, vs),
                    {"tokens": t}, cfg),
                donate=(1, 2, 3, 4), **sh["decode"])
        else:
            self._step_fn = _jit(
                placement,
                lambda p, k, v, rows, cur, nn, t: tfm.unified_step(
                    p, SlotPoolView(k, v, rows, cur, nn), {"tokens": t},
                    cfg),
                donate=(1, 2), **sh["step"])
            self._decode_fn = _jit(
                placement,
                lambda p, k, v, pos, t: tfm.unified_step(
                    p, SlotPoolView(k, v, None, pos, jnp.ones_like(pos)),
                    {"tokens": t}, cfg),
                donate=(1, 2), **sh["decode"])

    def _arena_args(self):
        p = self.pool
        if self.quantized:
            return (p.k, p.v, p.k_scale, p.v_scale)
        return (p.k, p.v)

    def step_chunk(self, rows, lanes, cur, n_new, tokens):
        logits, arenas = self._step_fn(self.params, *self._arena_args(),
                                       lanes, cur, n_new, tokens)
        self.pool.adopt(*arenas)
        return logits

    def step_decode(self, tokens, active):
        if self.kv_layout == "paged":
            logits, arenas = self._decode_fn(
                self.params, *self._arena_args(), self.pool.block_tables,
                self.pool.pos, tokens)
        else:
            logits, arenas = self._decode_fn(
                self.params, *self._arena_args(), self.pool.pos, tokens)
        self.pool.adopt(*arenas)
        return logits


# --------------------------------------------------------------------------
# ssm (xLSTM): recurrent state slots only — no KV anywhere
# --------------------------------------------------------------------------

class RecurrentAdapter(FamilyAdapter):
    def __init__(self, cfg, params, placement, psh, *, n_slots, max_len):
        self.cfg, self.params = cfg, params
        self.pool = RecurrentStatePool(
            cfg, n_slots, max_len,
            lambda c, n: xls.init_state(c, n), placement=placement)
        rep = placement.replicated
        ssh = placement.state_shardings(self.pool.states)
        self._step_fn = _jit(
            placement,
            lambda p, st, rows, cur, nn, t: xls.unified_step(
                p, RecurrentStateView(st, rows, cur, nn), {"tokens": t}, cfg),
            donate=(1,),
            in_shardings=(psh, ssh, rep, rep, rep, rep),
            out_shardings=(rep, ssh))
        self._decode_fn = _jit(
            placement,
            lambda p, st, pos, act, t: xls.unified_step(
                p, RecurrentStateView(st, None, pos, act), {"tokens": t},
                cfg),
            donate=(1,),
            in_shardings=(psh, ssh, rep, rep, rep),
            out_shardings=(rep, ssh))

    def step_chunk(self, rows, lanes, cur, n_new, tokens):
        logits, states = self._step_fn(self.params, self.pool.states, lanes,
                                       cur, n_new, tokens)
        self.pool.adopt(states)
        return logits

    def step_decode(self, tokens, active):
        # inactive lanes (mid-prefill rows, free slots) decode with
        # n_new=0: their gates are fully masked and their state leaves
        # come back bitwise untouched — unlike KV there is no
        # overwrite-before-read safety net for a recurrence
        act = np.zeros((self.pool.n_slots,), np.int32)
        act[active] = 1
        logits, states = self._decode_fn(self.params, self.pool.states,
                                         self.pool.pos, jnp.asarray(act),
                                         tokens)
        self.pool.adopt(states)
        return logits

    def save_for_preempt(self, req, slot, n_written):
        return {"state": self.pool.save_slot(slot), "pos": n_written}

    def on_admit(self, req, slot):
        if req.swap is None:
            return 0
        blob, req.swap = req.swap, None
        self.pool.restore_slot(slot, blob["state"])
        return blob["pos"]


# --------------------------------------------------------------------------
# hybrid (Zamba2): shared-attention KV pool + mamba state slots, one identity
# --------------------------------------------------------------------------

class HybridStatePool:
    """One slot identity across a KV pool (sized to the shared-attention
    applications) and a recurrent-state pool (all mamba layers).

    The engine drives the usual pool protocol; allocation and release hit
    both sub-pools in lockstep (slot layout) so the same id indexes a
    request's KV rows and its state leaves.  Everything else — lane maps,
    capacity checks, positions, the whole paged admission surface —
    forwards to the KV pool, whose per-row position doubles as the state
    cursor (tokens absorbed == tokens written, every layer sees every
    token once).  Under the paged layout rows come from ``admit`` and the
    state arena is simply indexed by row: stale state at a reused row is
    dead weight the in-jit cursor==0 init-select never reads.
    """

    def __init__(self, kv, state, paged: bool):
        self.kv = kv
        self.state = state
        self._paged = paged

    def __getattr__(self, name):
        return getattr(self.kv, name)

    def alloc(self):
        row = self.kv.alloc()
        if row is None:
            return None
        srow = self.state.alloc()
        if srow != row:
            raise CachePoolError(
                f"hybrid sub-pools desynchronized: kv slot {row} vs state "
                f"slot {srow}")
        return row

    def release(self, slot: int) -> None:
        self.kv.release(slot)
        if not self._paged:
            self.state.release(slot)

    free = release


class HybridAdapter(FamilyAdapter):
    def __init__(self, cfg, params, placement, psh, *, kv_layout, n_slots,
                 max_len, block_size, n_blocks, prefix_caching,
                 paged_attn_backend):
        n_attn = cfg.n_layers // cfg.attn_every if cfg.attn_every else 0
        if n_attn == 0:
            raise ValueError(
                "hybrid serving needs at least one shared-attention "
                "application (attn_every > 0); a pure-mamba stack should "
                "use the 'ssm' family path")
        self.cfg, self.params, self.kv_layout = cfg, params, kv_layout
        kv_cfg = dataclasses.replace(cfg, n_layers=n_attn)
        state = RecurrentStatePool(
            cfg, n_slots, max_len,
            lambda c, n: [zam.lane_init(c, i, n) for i in range(c.n_layers)],
            placement=placement)
        if kv_layout == "paged":
            # prefix caching is structurally off: a cached KV block cannot
            # reconstruct the SSM state that absorbed those tokens, so a
            # "hit" would resume with a state that never saw its prefix
            kv = PagedKVPool(kv_cfg, n_slots, max_len, block_size=block_size,
                             n_blocks=n_blocks, prefix_caching=False,
                             placement=placement)
        else:
            kv = SlotKVPool(kv_cfg, n_slots, max_len, placement=placement)
        self.pool = HybridStatePool(kv, state, paged=(kv_layout == "paged"))
        rep = placement.replicated
        kvsh = placement.kv
        ssh = placement.state_shardings(state.states)
        out_sh = (rep, (kvsh, kvsh), ssh)
        if kv_layout == "paged":
            trash = kv.trash_block
            self._step_fn = _jit(
                placement,
                lambda p, k, v, st, bt, srows, cur, nn, t: zam.unified_step(
                    p, HybridPoolView(PagedPoolView(k, v, bt, cur, nn, trash),
                                      RecurrentStateView(st, srows, cur, nn)),
                    {"tokens": t}, cfg, attn_backend=paged_attn_backend),
                donate=(1, 2, 3),
                in_shardings=(psh, kvsh, kvsh, ssh, rep, rep, rep, rep, rep),
                out_shardings=out_sh)
            self._decode_fn = _jit(
                placement,
                lambda p, k, v, st, bt, pos, act, t: zam.unified_step(
                    p, HybridPoolView(
                        PagedPoolView(k, v, bt, pos, jnp.ones_like(pos),
                                      trash),
                        RecurrentStateView(st, None, pos, act)),
                    {"tokens": t}, cfg, attn_backend=paged_attn_backend),
                donate=(1, 2, 3),
                in_shardings=(psh, kvsh, kvsh, ssh, rep, rep, rep, rep),
                out_shardings=out_sh)
        else:
            self._step_fn = _jit(
                placement,
                lambda p, k, v, st, rows, cur, nn, t: zam.unified_step(
                    p, HybridPoolView(SlotPoolView(k, v, rows, cur, nn),
                                      RecurrentStateView(st, rows, cur, nn)),
                    {"tokens": t}, cfg),
                donate=(1, 2, 3),
                in_shardings=(psh, kvsh, kvsh, ssh, rep, rep, rep, rep),
                out_shardings=out_sh)
            self._decode_fn = _jit(
                placement,
                lambda p, k, v, st, pos, act, t: zam.unified_step(
                    p, HybridPoolView(
                        SlotPoolView(k, v, None, pos, jnp.ones_like(pos)),
                        RecurrentStateView(st, None, pos, act)),
                    {"tokens": t}, cfg),
                donate=(1, 2, 3),
                in_shardings=(psh, kvsh, kvsh, ssh, rep, rep, rep),
                out_shardings=out_sh)

    def step_chunk(self, rows, lanes, cur, n_new, tokens):
        kv, st = self.pool.kv, self.pool.state
        if self.kv_layout == "paged":
            srows = jnp.asarray(st.lane_rows(rows, tokens.shape[0]))
            logits, (k, v), states = self._step_fn(
                self.params, kv.k, kv.v, st.states, lanes, srows, cur,
                n_new, tokens)
        else:
            logits, (k, v), states = self._step_fn(
                self.params, kv.k, kv.v, st.states, lanes, cur, n_new,
                tokens)
        kv.adopt(k, v)
        st.adopt(states)
        return logits

    def step_decode(self, tokens, active):
        kv, st = self.pool.kv, self.pool.state
        act = np.zeros((kv.n_slots,), np.int32)
        act[active] = 1
        if self.kv_layout == "paged":
            logits, (k, v), states = self._decode_fn(
                self.params, kv.k, kv.v, st.states, kv.block_tables,
                kv.pos, jnp.asarray(act), tokens)
        else:
            logits, (k, v), states = self._decode_fn(
                self.params, kv.k, kv.v, st.states, kv.pos,
                jnp.asarray(act), tokens)
        kv.adopt(k, v)
        st.adopt(states)
        return logits

    def save_for_preempt(self, req, slot, n_written):
        if self.kv_layout == "paged":
            return None                      # recompute (module docstring)
        kv, st = self.pool.kv, self.pool.state
        return {"state": st.save_slot(slot), "k": kv.k[:, slot],
                "v": kv.v[:, slot], "pos": n_written}

    def on_admit(self, req, slot):
        if req.swap is None:
            return 0
        blob, req.swap = req.swap, None
        kv, st = self.pool.kv, self.pool.state
        st.restore_slot(slot, blob["state"])
        kv.adopt(kv.k.at[:, slot].set(blob["k"].astype(kv.k.dtype)),
                 kv.v.at[:, slot].set(blob["v"].astype(kv.v.dtype)))
        return blob["pos"]


# --------------------------------------------------------------------------
# encdec (Whisper): decoder KV slots + read-only encoder context rows
# --------------------------------------------------------------------------

class EncDecAdapter(FamilyAdapter):
    def __init__(self, cfg, params, placement, psh, *, n_slots, max_len,
                 max_ctx):
        self.cfg, self.params = cfg, params
        self.pool = SlotKVPool(cfg, n_slots, max_len, placement=placement)
        self.ctx = EncoderContextPool(cfg, n_slots, max_ctx,
                                      placement=placement)
        rep, kvsh = placement.replicated, placement.kv
        # retraced once per distinct encoder length — padding is not an
        # option for a bidirectional encoder (every position attends to
        # every other), so admission runs at the TRUE length
        self._encode_fn = _jit(
            placement, lambda p, e: whi.encode_ctx(p, e, cfg),
            in_shardings=(psh, rep), out_shardings=(kvsh, kvsh))
        # ck/cv ride through WITHOUT donation: the context rows are read-
        # only for a request's whole lifetime and shared across steps
        self._step_fn = _jit(
            placement,
            lambda p, k, v, ck, cv, cl, rows, cur, nn, t: whi.unified_step(
                p, EncDecPoolView(k=k, v=v, rows=rows, cursor=cur, n_new=nn,
                                  ck=ck, cv=cv, ctx_len=cl),
                {"tokens": t}, cfg),
            donate=(1, 2),
            in_shardings=(psh, kvsh, kvsh, kvsh, kvsh, rep, rep, rep, rep,
                          rep),
            out_shardings=(rep, (kvsh, kvsh)))
        self._decode_fn = _jit(
            placement,
            lambda p, k, v, ck, cv, cl, pos, t: whi.unified_step(
                p, EncDecPoolView(k=k, v=v, rows=None, cursor=pos,
                                  n_new=jnp.ones_like(pos), ck=ck, cv=cv,
                                  ctx_len=cl),
                {"tokens": t}, cfg),
            donate=(1, 2),
            in_shardings=(psh, kvsh, kvsh, kvsh, kvsh, rep, rep, rep),
            out_shardings=(rep, (kvsh, kvsh)))

    def step_chunk(self, rows, lanes, cur, n_new, tokens):
        pool, ctx = self.pool, self.ctx
        clen = jnp.asarray(ctx.lane_lens(rows, tokens.shape[0]))
        logits, (k, v) = self._step_fn(self.params, pool.k, pool.v, ctx.ck,
                                       ctx.cv, clen, lanes, cur, n_new,
                                       tokens)
        pool.adopt(k, v)
        return logits

    def step_decode(self, tokens, active):
        pool, ctx = self.pool, self.ctx
        logits, (k, v) = self._decode_fn(self.params, pool.k, pool.v,
                                         ctx.ck, ctx.cv,
                                         jnp.asarray(ctx.lens), pool.pos,
                                         tokens)
        pool.adopt(k, v)
        return logits

    def validate_submit(self, prompt, sampling, embeds):
        if embeds is None:
            raise ValueError(
                "the enc-dec family needs embeds= at submit: the encoder "
                "frontend's [S_enc, d] features, run once at admission")
        n = np.asarray(embeds).shape[0]
        if n > self.ctx.max_ctx:
            raise ValueError(
                f"encoder input of {n} frames exceeds the context arena "
                f"(max_ctx={self.ctx.max_ctx})")

    def on_admit(self, req, slot):
        if req.swap is not None:
            blob, req.swap = req.swap, None
            self.ctx.restore_slot(slot, blob["ctx"])
            pool = self.pool
            pool.adopt(pool.k.at[:, slot].set(blob["k"].astype(pool.k.dtype)),
                       pool.v.at[:, slot].set(blob["v"].astype(pool.v.dtype)))
            return blob["pos"]
        emb = jnp.asarray(req.embeds, self.cfg.dtype)[None]    # [1, Se, d]
        ck, cv = self._encode_fn(self.params, emb)
        self.ctx.write(slot, ck[:, 0], cv[:, 0])
        return 0

    def save_for_preempt(self, req, slot, n_written):
        pool = self.pool
        return {"ctx": self.ctx.save_slot(slot), "k": pool.k[:, slot],
                "v": pool.v[:, slot], "pos": n_written}


# --------------------------------------------------------------------------

def build_adapter(cfg, params, placement, psh, *, kv_layout, n_slots,
                  max_len, block_size, n_blocks, prefix_caching,
                  paged_attn_backend, max_ctx=None, kv_dtype="bf16"):
    """The family's adapter, with its effective kv_layout resolved.

    ssm has no KV at all, so any requested layout coerces to "slot" (a
    layout over nothing); encdec pages neither its decoder slots nor its
    read-only context rows and rejects "paged" explicitly.  Quantized KV
    (``kv_dtype="int8"``) exists only for the pure KV-transformer
    families — recurrent/hybrid/encdec state blobs stay at model dtype.
    """
    fam = cfg.family
    if kv_dtype != "bf16" and fam not in ("dense", "moe"):
        raise ValueError(
            f"kv_dtype={kv_dtype!r} needs a KV-transformer family "
            f"(dense/moe), not {fam!r}")
    if fam in ("dense", "moe"):
        return TransformerAdapter(
            cfg, params, placement, psh, kv_layout=kv_layout,
            n_slots=n_slots, max_len=max_len, block_size=block_size,
            n_blocks=n_blocks, prefix_caching=prefix_caching,
            paged_attn_backend=paged_attn_backend, kv_dtype=kv_dtype)
    if fam == "ssm":
        return RecurrentAdapter(cfg, params, placement, psh,
                                n_slots=n_slots, max_len=max_len)
    if fam == "hybrid":
        return HybridAdapter(
            cfg, params, placement, psh, kv_layout=kv_layout,
            n_slots=n_slots, max_len=max_len, block_size=block_size,
            n_blocks=n_blocks, prefix_caching=prefix_caching,
            paged_attn_backend=paged_attn_backend)
    if fam == "encdec":
        if kv_layout == "paged":
            raise ValueError(
                "the enc-dec family has no paged layout: decoder KV is "
                "slot-resident and the encoder context rows are read-only")
        return EncDecAdapter(cfg, params, placement, psh, n_slots=n_slots,
                             max_len=max_len,
                             max_ctx=max_ctx if max_ctx is not None
                             else max_len)
    raise ValueError(f"no serving adapter for family {fam!r}")
