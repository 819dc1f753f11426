"""Decoder-only transformer (dense / MoE / VLM backbone).

Covers: gemma-7b (GeGLU, head_dim 256), qwen3-8b (qk_norm), internlm2-1.8b,
nemotron-4-340b (squared-ReLU, non-gated), qwen2-vl-72b (M-RoPE, embed-input),
mixtral-8x7b (MoE + SWA), llama4-maverick (MoE top-1 + shared expert), and the
paper's llama2/3 + mistral models.

Layer weights are stacked [L, ...]; the forward pass runs either
``lax.scan`` over layers (training: fast compile, remat-able) or an unrolled
Python loop (``unroll=True``, used by the dry-run so XLA cost analysis counts
every layer — see DESIGN.md §6).

Entry points per the launch contract:
  loss_fn(params, batch, cfg)                          — training
  prefill(params, batch, cfg) -> (logits, caches)      — inference prefill
  decode_lockstep(params, caches, batch, cfg)          — lock-step decode

Serving runs on ONE attention path: ``unified_step`` /
``attend_over_pool``.  Every serving step — chunked prefill (q_len =
chunk), one-shot prefill (q_len = prompt, cursor = 0), and fused decode
(q_len = 1) — writes its fresh KV into the engine's KV arena (slot rows or
paged blocks, addressed by a pool view from ``serving/cache_pool.py`` /
``serving/paged/pool.py``) and attends IN PLACE against that arena with
the per-request cursor as a length mask.  Nothing ever gathers a copy of
the already-written prefix, so a prefill chunk's HBM traffic is
independent of how much prefix the request has written — O(P) total over
a P-token prompt instead of the O(P^2/budget) the old gather-based
chunk path paid.  ``decode_lockstep`` is a thin adapter over the same
primitive for the legacy lock-step loop, and zamba's shared-attention
block runs ``_block_step`` itself, so there is exactly one masking /
RoPE-offset / write implementation.  The arena is never copied whole:
it rides the layer loop as carried state and each layer writes into it
at its own index.

Speculative verify is the same entry with q_len = k+1: the engine feeds
``[last_token, d1..dk]`` at cursor = written-prefix length and reads a
distribution per position from one call — the per-query causal length
mask makes the batched scoring bitwise-identical to k+1 sequential
decode steps, which is what makes greedy speculative decoding
token-identical to the non-speculative stream (serving/speculative.py).

Sharding: the forward/decode paths are placement-agnostic.  Training and
the dry-run shard through the activation policy (parallel/policy.py, a
no-op when inactive); the serving engine instead commits params and KV
arenas to explicit NamedShardings (serving/placement.py) and lets GSPMD
propagate, so the same code serves single-device and tensor-parallel.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .layers import (activation, apply_rope, attend_length_masked,
                     dense_init, linear, rms_norm, sdpa, split_keys)
from . import moe as moe_lib


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(key, cfg, scale_layers: bool = True):
    d, hd, H, KV, L = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    dtype = cfg.dtype
    ks = split_keys(key, 12)

    def stack(initf, *shape_key):
        outs = [initf(k) for k in split_keys(shape_key[0], L)]
        return jnp.stack(outs)

    layers = {
        "attn_norm": jnp.zeros((L, d), dtype),
        "wq": stack(lambda k: dense_init(k, H * hd, d, dtype), ks[0]),
        "wk": stack(lambda k: dense_init(k, KV * hd, d, dtype), ks[1]),
        "wv": stack(lambda k: dense_init(k, KV * hd, d, dtype), ks[2]),
        "wo": stack(lambda k: dense_init(k, d, H * hd, dtype), ks[3]),
        "mlp_norm": jnp.zeros((L, d), dtype),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.zeros((L, hd), dtype)
        layers["k_norm"] = jnp.zeros((L, hd), dtype)
    if cfg.moe is not None:
        sub = [moe_lib.init_moe(k, cfg, dtype) for k in split_keys(ks[4], L)]
        layers["moe"] = jax.tree.map(lambda *xs: jnp.stack(xs), *sub)
    else:
        if cfg.glu:
            layers["w_gate"] = stack(lambda k: dense_init(k, cfg.d_ff, d, dtype), ks[5])
        layers["w_up"] = stack(lambda k: dense_init(k, cfg.d_ff, d, dtype), ks[6])
        layers["w_down"] = stack(lambda k: dense_init(k, d, cfg.d_ff, dtype), ks[7])

    params = {
        "embed": (jax.random.normal(ks[8], (cfg.vocab, d), jnp.float32)
                  * 0.02).astype(dtype),
        "layers": layers,
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[9], cfg.vocab, d, dtype)
    return params


# --------------------------------------------------------------------------
# one block
# --------------------------------------------------------------------------

def _project_qkv(lp, x, cfg, positions):
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = linear(lp["wq"], x).reshape(B, S, H, hd)
    k = linear(lp["wk"], x).reshape(B, S, KV, hd)
    v = linear(lp["wv"], x).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def _mlp(lp, x, cfg):
    from ..parallel import policy as pol
    if cfg.moe is not None:
        return moe_lib.moe_apply(lp["moe"], x, cfg)
    if cfg.glu:
        hidden = activation(cfg.act, linear(lp["w_gate"], x)) * linear(lp["w_up"], x)
    else:
        hidden = activation(cfg.act, linear(lp["w_up"], x))
    hidden = pol.shard(hidden, ("fsdp", None, "model"))
    return linear(lp["w_down"], hidden)


def block_forward(lp, x, positions, cfg, q_chunks: int = 1, causal: bool = True,
                  prior_kv=None):
    """Full-sequence block (train / legacy prefill). Returns (y, (k, v)).

    ``prior_kv`` = (k, v) [B, P, KV, hd] of an already-computed context:
    queries attend to prior + fresh keys with a ``q_offset`` of P, and
    only the fresh suffix KV is returned.  The serving engine no longer
    uses this (chunks attend in place via ``attend_over_pool``); it stays
    as the gather-style reference that benchmarks measure against.

    Activation constraints pin the batch (fsdp) sharding at block boundaries —
    without them GSPMD can flip to a d_model-sharded/batch-replicated layout
    whose temps are mesh-times larger (see DESIGN.md §Perf log)."""
    from ..parallel import policy as pol
    x = pol.shard(x, ("fsdp", None, None))
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = _project_qkv(lp, h, cfg, positions)
    q = pol.shard(q, ("fsdp", None, "model", None))
    if prior_kv is not None:
        pk, pv = prior_kv
        k_all = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        attn = sdpa(q, k_all, v_all, causal=causal, window=cfg.window,
                    q_chunks=q_chunks, q_offset=pk.shape[1])
    else:
        attn = sdpa(q, k, v, causal=causal, window=cfg.window,
                    q_chunks=q_chunks)
    x = x + linear(lp["wo"], attn.reshape(*attn.shape[:2], -1))
    x = pol.shard(x, ("fsdp", None, None))
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + _mlp(lp, h, cfg)
    return x, (k, v)


# --------------------------------------------------------------------------
# the unified serving attention path: write into the pool, attend in place
# --------------------------------------------------------------------------

def _cursor_vec(pos, B: int):
    """[B] int32 cursor from a scalar (lock-step) or per-row position."""
    if jnp.ndim(pos) == 1:
        return pos.astype(jnp.int32)
    return jnp.broadcast_to(pos, (B,)).astype(jnp.int32)


def _pool_positions(cursor, S: int, cfg):
    """RoPE positions for S fresh tokens per lane starting at ``cursor``
    — [B, S], or [3, B, S] under M-RoPE (t/h/w share the text position
    on the serving path)."""
    base = cursor[:, None] + jnp.arange(S)[None]
    if cfg.mrope_sections is not None:
        return jnp.broadcast_to(base[None], (3,) + base.shape)
    return base


def attend_over_pool(q, pool_view, layer, *, window: int | None = None,
                     backend: str | None = None):
    """THE serving attention primitive: ``q`` [B, S, H, hd] attends
    directly against layer ``layer`` of a KV pool arena — slot rows or
    paged blocks — with the per-request cursor as a length mask.

    ``pool_view`` is a ``SlotPoolView`` / ``PagedPoolView``
    (serving/cache_pool.py, serving/paged/pool.py) whose ``k``/``v`` hold
    the whole [L, ...] arenas and whose addressing fields say where each
    batch lane's sequence lives.  Query i of lane b sits at absolute
    position ``cursor[b] + i`` and sees arena positions ``j <= cursor[b]
    + i`` (window-limited), which is exactly right because the step's
    fresh KV was scattered at the cursor before attending — causality
    then hides this step's not-yet-visible writes, stale tokens of
    previous slot/block occupants, and padding, so chunked prefill (S =
    chunk), one-shot prefill (S = prompt, cursor = 0), and fused decode
    (S = 1) are all the same computation.

    Never materializes gathered prefix context: per-step prefix HBM
    traffic is bounded by the arena rows/blocks touched, independent of
    how much prefix each lane has already written.
    """
    pv = pool_view
    if pv.block_tables is not None:
        from ..serving.paged.paged_attention import paged_attention
        return paged_attention(q, pv.k, pv.v, pv.block_tables, pv.cursor,
                               layer=layer, window=window, backend=backend,
                               k_scale=pv.k_scale, v_scale=pv.v_scale)
    k_rows, v_rows = pv.lane_kv(pv.k, pv.v, layer)
    ks = vs = None
    if pv.k_scale is not None:
        ks, vs = pv.lane_kv(pv.k_scale, pv.v_scale, layer)
    return attend_length_masked(q, k_rows, v_rows, pv.cursor, window=window,
                                k_scale=ks, v_scale=vs)


def _arenas(view) -> tuple:
    """The arenas a pool view carries: (k, v, k_scale, v_scale), the
    scales None for a bf16 arena."""
    return view.k, view.v, view.k_scale, view.v_scale


def _block_step(lp, x, view, layer, positions, cfg, attn_backend):
    """One block of the unified step: project q/k/v at the lane cursor
    positions, scatter the fresh KV into layer ``layer`` of the view's
    whole arenas (in place under donation; an int8 arena quantizes on
    scatter), and attend over the pool.  Returns (y, view) with the view
    holding the updated arenas."""
    from ..parallel import policy as pol
    B, S, _ = x.shape
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = _project_qkv(lp, h, cfg, positions)
    q = pol.shard(q, ("fsdp", None, "model", None))
    with jax.named_scope("kv_write"):
        view = view.write_layer(layer, k, v)
    attn = attend_over_pool(q, view, layer, window=cfg.window,
                            backend=attn_backend)
    x = x + linear(lp["wo"], attn.reshape(B, S, -1))
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + _mlp(lp, h, cfg)
    return x, view


def unified_step(params, view, batch, cfg, *, attn_backend=None,
                 unroll: bool = False):
    """One attend-in-place step over a KV pool: the only serving
    attention path.

    ``batch["tokens"]`` [B, S] are the next S tokens of each lane,
    starting at ``view.cursor`` (per-lane RoPE/mask offset — chunk token
    i sits at absolute position cursor + i).  The whole [L, ...] arenas
    ride the layer loop as carried state: each layer scatters its fresh
    KV at (layer, slot) and attention reads the arena directly with the
    cursor as a length mask.  The engine donates the arenas, so the
    multi-GB buffers update in place; no layer of them is sliced out or
    stacked back.  Covers every serving shape: S = prompt & cursor = 0 is
    one-shot prefill, S = chunk is chunked prefill (numerically the
    one-shot prefill it replaces), and S = 1 over all lanes is the fused
    decode.

    Returns (logits [B, S, V], (k, v)) — the updated [L, ...] arenas —
    or (logits, (k, v, k_scale, v_scale)) when the view carries an int8
    arena's scale tensors.
    """
    from ..parallel import policy as pol
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = _pool_positions(view.cursor, S, cfg)
    x = pol.shard(x, ("fsdp", None, None))

    def layer_step(x, arenas, lp, i):
        k, v, ks, vs = arenas
        x, out = _block_step(lp, x, dataclasses.replace(
            view, k=k, v=v, k_scale=ks, v_scale=vs), i, positions, cfg,
            attn_backend)
        return x, _arenas(out)

    arenas = _arenas(view)
    if unroll:
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda p: p[i], params["layers"])
            x, arenas = layer_step(x, arenas, lp, i)
    else:
        def body(carry, xs):
            return layer_step(*carry, *xs), None
        (x, arenas), _ = jax.lax.scan(
            body, (x, arenas),
            (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = pol.shard(linear(head, x), ("fsdp", None, "model"))
    return logits, tuple(a for a in arenas if a is not None)


def decode_lockstep(params, caches, batch, cfg, unroll: bool = False):
    """One new token for every sequence through the unified primitive —
    the model-zoo decode contract for the legacy lock-step loop and the
    dry-run.  batch: {"tokens": [B, 1]}.

    caches: {"k"/"v": [L, B, Smax, KV, hd], "pos": filled length — a
    scalar (lock-step batch) or a [B] vector (each row at its own
    sequence position)}.  The [B, Smax] cache layout IS a slot arena with
    one slot per row, so this is ``unified_step`` with an identity lane
    map and S = 1.
    """
    from ..serving.cache_pool import SlotPoolView
    tokens = batch["tokens"]
    B = tokens.shape[0]
    pos = caches["pos"]
    view = SlotPoolView(k=caches["k"], v=caches["v"], rows=None,
                        cursor=_cursor_vec(pos, B),
                        n_new=jnp.ones((B,), jnp.int32))
    logits, (k, v) = unified_step(params, view, {"tokens": tokens}, cfg,
                                  unroll=unroll)
    return logits[:, -1], {"k": k, "v": v, "pos": pos + 1}


# --------------------------------------------------------------------------
# full forward
# --------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg):
    """Returns (x [B,S,d], positions)."""
    if "embeds" in batch:                      # vlm / audio stub frontend
        x = batch["embeds"].astype(cfg.dtype)
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
    if "positions" in batch:
        positions = batch["positions"]
    elif cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        positions = jnp.broadcast_to(positions[None], (3, B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return x, positions


def _auto_q_chunks(S: int) -> int:
    return max(1, S // 4096) if S > 8192 else 1


def forward(params, batch, cfg, unroll: bool = False, collect_kv: bool = False):
    """Full-sequence forward. Returns (logits, caches|None)."""
    from ..parallel import policy as pol
    x, positions = _embed_inputs(params, batch, cfg)
    x = pol.shard(x, ("fsdp", None, None))
    q_chunks = _auto_q_chunks(x.shape[1])

    blk = partial(block_forward, positions=positions, cfg=cfg, q_chunks=q_chunks)
    if unroll:
        ublk = jax.checkpoint(blk) if (cfg.remat and not collect_kv) else blk
        kvs = []
        L = cfg.n_layers
        for i in range(L):
            lp = jax.tree.map(lambda p: p[i], params["layers"])
            x, kv = ublk(lp, x)
            if collect_kv:
                kvs.append(kv)
        caches = _stack_kv(kvs) if collect_kv else None
    else:
        def body(h, lp):
            h, kv = blk(lp, h)
            return h, kv if collect_kv else None
        fn = jax.checkpoint(body) if (cfg.remat and not collect_kv) else body
        x, kvs = jax.lax.scan(fn, x, params["layers"])
        caches = (kvs[0], kvs[1]) if collect_kv else None

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = pol.shard(linear(head, x), ("fsdp", None, "model"))
    return logits, caches


def _stack_kv(kvs):
    k = jnp.stack([kv[0] for kv in kvs])
    v = jnp.stack([kv[1] for kv in kvs])
    return (k, v)


# --------------------------------------------------------------------------
# launch contract
# --------------------------------------------------------------------------

def loss_fn(params, batch, cfg, unroll: bool = False):
    logits, _ = forward(params, batch, cfg, unroll=unroll)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    aux = {}
    if cfg.moe is not None:
        # load-balance aux on the input embeddings of each layer is costly to
        # recover post-hoc; use first-layer input as proxy signal.
        x, _ = _embed_inputs(params, batch, cfg)
        lp0 = jax.tree.map(lambda p: p[0], params["layers"])
        aux["lb_loss"] = moe_lib.aux_load_balance_loss(lp0["moe"], x, cfg)
        loss = loss + 0.01 * aux["lb_loss"]
    return loss, aux


def init_cache(cfg, batch_size: int, max_len: int):
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    shape = (L, batch_size, max_len, KV, hd)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype),
            "pos": jnp.zeros((), jnp.int32)}


def prefill(params, batch, cfg, unroll: bool = False):
    """Run the full prompt; return (last-token logits, filled caches)."""
    logits, (k, v) = forward(params, batch, cfg, unroll=unroll, collect_kv=True)
    S = k.shape[2]
    caches = {"k": k, "v": v, "pos": jnp.array(S, jnp.int32)}
    return logits[:, -1], caches
