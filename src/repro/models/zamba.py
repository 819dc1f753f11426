"""Mamba2 (SSD) blocks + Zamba2 hybrid (arXiv:2411.15242).

Mamba2 block (Dao & Gu 2024, simplified — no causal conv, noted in DESIGN):
  x -> in_proj -> (z [di], xc [di], B [N], C [N], dt [H])  with di = 2*d,
  H = di/head_dim heads, N = ssm_state.
  scalar-decay recurrence per head:  h' = exp(dt*A) h + dt * B x
  -> shared chunkwise engine (linear_attn.chunked_gla) with
     q=C, k=B (broadcast over heads), v=dt*x, log_f=dt*A.
  y = (ssd_out + D*xc) * silu(z); out_proj; residual.

Zamba2 hybrid: ``cfg.n_layers`` Mamba2 blocks; ONE shared transformer block
(full attention + MLP, single weight set) applied after every
``cfg.attn_every`` Mamba2 blocks — weight sharing across applications is the
Zamba signature; each application has its own KV cache.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .layers import dense_init, linear, rms_norm, split_keys
from .linear_attn import chunked_gla, masked_gates
from . import transformer as tfm


def _dims(cfg):
    di = 2 * cfg.d_model
    H = di // cfg.ssm_head_dim
    return di, H, cfg.ssm_state


def init_params(key, cfg):
    d, L = cfg.d_model, cfg.n_layers
    di, H, N = _dims(cfg)
    dtype = cfg.dtype
    ks = split_keys(key, 8)

    def stack(initf, key):
        return jnp.stack([initf(k) for k in split_keys(key, L)])

    proj_out = 2 * di + 2 * N + H
    mamba = {
        "norm": jnp.zeros((L, d), dtype),
        "in_proj": stack(lambda k: dense_init(k, proj_out, d, dtype), ks[0]),
        "out_proj": stack(lambda k: dense_init(k, d, di, dtype), ks[1]),
        "A_log": jnp.zeros((L, H), jnp.float32),       # A = -exp(A_log)
        "D": jnp.ones((L, H), jnp.float32),
        "dt_bias": jnp.zeros((L, H), jnp.float32),
    }
    params = {
        "embed": (jax.random.normal(ks[2], (cfg.vocab, d), jnp.float32) * 0.02
                  ).astype(dtype),
        "mamba": mamba,
        "final_norm": jnp.zeros((d,), dtype),
        "lm_head": dense_init(ks[3], cfg.vocab, d, dtype),
    }
    if cfg.attn_every:
        # ONE shared attention+MLP block (Zamba2 signature)
        shared_cfg = cfg
        sub = tfm.init_params(jax.random.fold_in(ks[4], 1),
                              _shared_block_cfg(cfg))
        params["shared_attn"] = jax.tree.map(lambda p: p[0], sub["layers"])
    return params


def _shared_block_cfg(cfg):
    import dataclasses
    return dataclasses.replace(cfg, n_layers=1, moe=None, family="dense")


def _ssm_inputs(lp, x, cfg):
    """x: [B,S,d] -> z, q(C), k(B), v(dt*xc), log_f, xc_heads."""
    di, H, N = _dims(cfg)
    B_, S = x.shape[:2]
    proj = linear(lp["in_proj"], x)
    z, xc, Bv, Cv, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])   # [B,S,H]
    A = -jnp.exp(lp["A_log"])                                      # [H]
    log_f = dt * A[None, None]                                     # <= 0
    xh = xc.reshape(B_, S, H, cfg.ssm_head_dim)
    v = xh * dt[..., None].astype(xh.dtype)
    q = jnp.broadcast_to(Cv[:, :, None], (B_, S, H, N))
    k = jnp.broadcast_to(Bv[:, :, None], (B_, S, H, N))
    return z, q, k, v, log_f, xh


def mamba_block(lp, x, cfg, state=None, chunk: int = 128, valid=None):
    from ..parallel import policy as pol
    B_, S, d = x.shape
    di, H, N = _dims(cfg)
    x = pol.shard(x, ("fsdp", None, None))
    h = rms_norm(x, lp["norm"], cfg.norm_eps)
    z, q, k, v, log_f, xh = _ssm_inputs(lp, h, cfg)
    z = pol.shard(z, ("fsdp", None, "model"))
    log_i = None
    if valid is not None:
        # right-padded serving batch: neutral gates keep the carried SSM
        # state bit-identical to processing the real prefix alone
        log_f, log_i = masked_gates(log_f, log_i, valid)
    y, new_state = chunked_gla(q, k, v, log_f, log_i, chunk=chunk,
                               normalizer=False, initial_state=state)
    y = y + xh * lp["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B_, S, di) * jax.nn.silu(z)
    return x + linear(lp["out_proj"], y), new_state


# ------------------------------------------------------------ full model ---

def _shared_positions(cfg, B, S, offset=0):
    return jnp.broadcast_to(jnp.arange(S)[None] + offset, (B, S))


def forward(params, batch, cfg, unroll: bool = False, states=None,
            return_states: bool = False):
    tokens = batch["tokens"]
    B_, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    new_states, kvs = [], []
    # Python layer loop (heterogeneous blocks): remat each block so backward
    # saves only the [B,S,d] block inputs, not every SSD intermediate.
    mamba_fn = jax.checkpoint(partial(mamba_block, cfg=cfg)) if cfg.remat \
        else partial(mamba_block, cfg=cfg)
    qc = max(1, S // 4096) if S > 8192 else 1
    attn_fn = partial(tfm.block_forward, cfg=_shared_block_cfg(cfg), q_chunks=qc)
    if cfg.remat:
        attn_fn = jax.checkpoint(attn_fn)
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda p: p[i], params["mamba"])
        st = states[i] if states is not None else None
        x, s = mamba_fn(lp, x, state=st)
        new_states.append(s)
        if cfg.attn_every and (i % cfg.attn_every) == (cfg.attn_every - 1):
            pos = _shared_positions(cfg, B_, S)
            x, kv = attn_fn(params["shared_attn"], x, pos)
            kvs.append(kv)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = linear(params["lm_head"], x)
    if return_states:
        return logits, (new_states, kvs)
    return logits, None


def loss_fn(params, batch, cfg, unroll: bool = False):
    logits, _ = forward(params, batch, cfg)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32), labels[..., None],
                               axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask) / jnp.maximum(mask.sum(), 1.0), {}


def prefill(params, batch, cfg, unroll: bool = False, max_len: int | None = None):
    """Returns caches with SSM states + per-application KV caches."""
    tokens = batch["tokens"]
    B_, S = tokens.shape
    max_len = max_len or S
    logits, (states, kvs) = forward(params, batch, cfg, return_states=True)
    # pad KV caches to max_len for decode
    def pad(kv):
        k, v = kv
        pad_width = [(0, 0), (0, max_len - k.shape[1]), (0, 0), (0, 0)]
        return (jnp.pad(k, pad_width), jnp.pad(v, pad_width))
    kvs = [pad(kv) for kv in kvs]
    return logits[:, -1], {"states": states, "kv": kvs,
                           "pos": jnp.array(S, jnp.int32)}


def init_cache(cfg, batch_size: int, max_len: int):
    di, H, N = _dims(cfg)
    states = [(jnp.zeros((batch_size, H, N, cfg.ssm_head_dim), jnp.float32), None)
              for _ in range(cfg.n_layers)]
    n_attn = cfg.n_layers // cfg.attn_every if cfg.attn_every else 0
    KV, hd = cfg.n_kv_heads, cfg.hd
    kvs = [(jnp.zeros((batch_size, max_len, KV, hd), cfg.dtype),
            jnp.zeros((batch_size, max_len, KV, hd), cfg.dtype))
           for _ in range(n_attn)]
    return {"states": states, "kv": kvs, "pos": jnp.zeros((), jnp.int32)}


def lane_init(cfg, i: int, batch_size: int):
    """Layer ``i``'s fresh SSM state for ``batch_size`` lanes (the
    per-layer unit of ``init_cache``'s states list)."""
    di, H, N = _dims(cfg)
    return (jnp.zeros((batch_size, H, N, cfg.ssm_head_dim), jnp.float32),
            None)


def unified_step(params, view, batch, cfg, *, attn_backend=None,
                 unroll: bool = False):
    """One serving step for the hybrid family over a ``HybridPoolView``:
    mamba layers run on the recurrent-state sub-view (``view.state``, gate
    masking + in-jit fresh-state select), shared-attention applications run
    on the KV sub-view (``view.kv`` — SlotPoolView OR PagedPoolView)
    through the same in-place block as the transformer engine, all inside
    ONE jitted step.  The sub-views carry independent ``n_new``: decode
    writes KV for every lane (overwritten-before-read, harmless) but masks
    state updates to active lanes.

    Returns (logits [B,S,V], (k, v) stacked [n_attn, ...] arenas | None,
    new state arenas)."""
    tokens = batch["tokens"]
    B_, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    sview, kview = view.state, view.kv
    valid = jnp.arange(S)[None, :] < sview.n_new[:, None]         # [B,S]
    took = sview.n_new > 0
    scfg = _shared_block_cfg(cfg)
    positions = tfm._pool_positions(kview.cursor, S, scfg) \
        if cfg.attn_every else None
    new_states = []
    ai = 0
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda p: p[i], params["mamba"])
        lane_st = sview.gather_layer(i)
        st = sview.select_fresh(lane_st, lane_init(cfg, i, B_))
        x, s = mamba_block(lp, x, cfg, state=st, valid=valid)
        s = jax.tree.map(
            lambda new, old: jnp.where(
                took.reshape(took.shape + (1,) * (new.ndim - 1)), new, old),
            s, lane_st)
        new_states.append(sview.scatter_layer(i, s))
        if cfg.attn_every and (i % cfg.attn_every) == (cfg.attn_every - 1):
            x, kview = tfm._block_step(params["shared_attn"], x, kview, ai,
                                       positions, scfg, attn_backend)
            ai += 1
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = linear(params["lm_head"], x)
    kv = (kview.k, kview.v) if ai else None
    return logits, kv, new_states


def decode_lockstep(params, caches, batch, cfg, unroll: bool = False):
    """Reference lock-step decode via ``unified_step`` (S=1, identity lane
    map) — same float operation order as the engine's fused decode."""
    from ..serving.cache_pool import SlotPoolView
    from ..serving.state_pool import HybridPoolView, RecurrentStateView
    tokens = batch["tokens"]
    B_ = tokens.shape[0]
    pos = caches["pos"]
    cursor = tfm._cursor_vec(pos, B_)
    ones = jnp.ones((B_,), jnp.int32)
    sview = RecurrentStateView(caches["states"], None, cursor, ones)
    kvs = caches["kv"]
    k = jnp.stack([kv[0] for kv in kvs]) if kvs else None
    v = jnp.stack([kv[1] for kv in kvs]) if kvs else None
    kview = SlotPoolView(k=k, v=v, rows=None, cursor=cursor, n_new=ones)
    logits, kv, states = unified_step(params, HybridPoolView(kview, sview),
                                      batch, cfg, unroll=unroll)
    new_kvs = [(kv[0][i], kv[1][i]) for i in range(len(kvs))] if kvs else []
    return logits[:, -1], {"states": states, "kv": new_kvs, "pos": pos + 1}
