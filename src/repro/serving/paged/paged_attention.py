"""Paged attention over block-mapped KV: decode (q_len == 1) AND chunked
prefill (q_len == S) through one entry point.

``q`` is [B, S, H, hd]; ``cursor`` [B] counts the tokens of each row that
were already visible before this step's S fresh ones.  Query i of row b
sits at absolute position ``cursor[b] + i`` and attends to sequence
positions ``j <= cursor[b] + i`` (window-limited) — the unified in-place
masking rule of ``models/transformer.attend_over_pool``: the fresh chunk's
KV is scattered into the arena *before* attention, so the causal mask
alone hides this step's not-yet-visible writes, stale tokens of previous
block occupants, and trash-block padding.  S=1 with ``cursor = pos``
reproduces the old decode contract (visible count ``pos + 1``).

Two implementations behind one entry point:

- ``reference``: gather the row's blocks into a contiguous
  ``[B, nb*block_size, KV, hd]`` view with ``arena[block_tables]`` and run
  the masked softmax.  A table maps sequence position ``p`` to gathered
  index ``p`` exactly, so the mask carries over unchanged — XLA fuses the
  gather, so this is also the portable CPU/GPU path.
- ``pallas``: a TPU kernel (interpret-mode fallback off-TPU) that never
  materializes the gathered view.  The block table rides in as a
  scalar-prefetch operand, the grid is ``(B, query tiles, nb)`` with
  blocks innermost, and each step DMAs exactly one physical KV block —
  the index map reads ``block_tables[b, j]`` — accumulating flash-style
  (running max / sum / weighted value in VMEM scratch) over one query
  tile of the row.  Query tiles (``_q_tile``) cap the q/out/accumulator
  tiles at ``_Q_ROWS`` query-head rows, so every model width and chunk
  size fits scoped VMEM with the same kernel.  HBM traffic is therefore
  proportional to the tokens a request has actually written, not to a
  reserved ``max_len``.

All paths mask with a finite ``-1e30`` (exp underflows to exactly 0.0
against any real row max), so masked positions contribute exactly nothing
and the result is comparable with the contiguous slot-arena attention.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...kernels import resolve_interpret

_NEG_INF = -1e30

# query x q-head rows of one grid step's q/out/accumulator tiles: 4096
# rows of hd=128 keep them, double-buffered, inside the 16 MiB of scoped
# VMEM on a v5e
_Q_ROWS = 4096


def _default_backend() -> str:
    env = os.environ.get("REPRO_PAGED_BACKEND")
    if env:
        return env
    # interpret-mode Pallas is a Python loop over the grid — fine for
    # validation, far too slow to serve from on CPU
    return "pallas" if jax.default_backend() == "tpu" else "reference"


# --------------------------------------------------------------------------
# reference (jnp gather)
# --------------------------------------------------------------------------

def paged_attention_ref(q, k_arena, v_arena, block_tables, cursor,
                        *, window: int | None = None,
                        k_scale=None, v_scale=None) -> jax.Array:
    """q [B,S,H,hd]; arenas [n_blocks, bs, KV, hd]; block_tables [B, nb]
    int32; cursor [B] (tokens visible per row before this step's S fresh
    ones).  Returns [B,S,H,hd].

    A table maps sequence position ``p`` to gathered index ``p`` exactly,
    so after the gather this IS the contiguous length-masked attention —
    delegated to ``models/layers.attend_length_masked`` so the masking
    rule lives in one place.  ``k_scale``/``v_scale`` [n_blocks, bs, KV]
    are an int8 arena's per-position scales, gathered through the same
    tables (XLA fuses gather + dequant — no bf16 arena copy)."""
    from ...models.layers import attend_length_masked
    B, S, H, hd = q.shape
    _, bs, KV, _ = k_arena.shape
    nb = block_tables.shape[1]
    k = k_arena[block_tables].reshape(B, nb * bs, KV, hd)
    v = v_arena[block_tables].reshape(B, nb * bs, KV, hd)
    ks = vs = None
    if k_scale is not None:
        ks = k_scale[block_tables].reshape(B, nb * bs, KV)
        vs = v_scale[block_tables].reshape(B, nb * bs, KV)
    return attend_length_masked(q, k, v, cursor, window=window,
                                k_scale=ks, v_scale=vs)


# --------------------------------------------------------------------------
# pallas kernel
# --------------------------------------------------------------------------

def _q_tile(S: int, H: int) -> int:
    """Queries per grid tile for ``H`` q heads: the whole chunk when it
    fits ``_Q_ROWS``, else the largest power of two within it that divides
    S, so the [S, H, hd] q/out tiles and the f32 accumulator stay inside
    scoped VMEM at any chunk size."""
    cap = 1 << max(_Q_ROWS // H, 1).bit_length() - 1
    return S if S <= cap else math.gcd(S, cap)


def _paged_attn_kernel(bt_ref, cur_ref, q_ref, k_ref, v_ref, *refs,
                       bs, nb, n_rep, window, quantized):
    if quantized:
        # int8 arenas: per-position scale tiles ride the same block-table
        # index map as the KV tiles and dequantize in-register, inside the
        # online softmax — the gathered bf16 KV never exists in HBM
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    b, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    S, hd = q_ref.shape[1], q_ref.shape[-1]
    qf = q_ref[0].astype(jnp.float32) / math.sqrt(hd)         # [S, H, hd]
    k = k_ref[0].astype(jnp.float32)                          # [bs, KV, hd]
    v = v_ref[0].astype(jnp.float32)
    if quantized:
        k = k * ks_ref[0][..., None]                          # [bs, KV, 1]
        v = v * vs_ref[0][..., None]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=1)                      # [bs, H, hd]
        v = jnp.repeat(v, n_rep, axis=1)
    s = jnp.einsum("qhd,thd->hqt", qf, k)                     # [H, S, bs]

    qpos = cur_ref[b] + qi * S + jax.lax.iota(jnp.int32, S)   # [S]
    idx = j * bs + jax.lax.iota(jnp.int32, bs)                # [bs]
    valid = idx[None, :] <= qpos[:, None]                     # [S, bs]
    if window is not None:
        valid &= idx[None, :] > qpos[:, None] - window
    s = jnp.where(valid[None], s, _NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]                   # [H, S]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])                         # [H, S, bs]
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=2)
    acc_ref[...] = (acc_ref[...] * alpha[..., None]
                    + jnp.einsum("hqt,thd->hqd", p, v))

    @pl.when(j == nb - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)                # [H, S]
        out = acc_ref[...] / denom[..., None]                 # [H, S, hd]
        o_ref[...] = out.transpose(1, 0, 2)[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_pallas(q, k_arena, v_arena, block_tables, cursor,
                           *, window: int | None = None,
                           interpret: bool | None = None,
                           k_scale=None, v_scale=None) -> jax.Array:
    """Same contract as ``paged_attention_ref``; one grid step per
    (row, query tile, block), KV blocks DMA'd by table lookup via scalar
    prefetch.  Long chunks walk query tiles (``_q_tile``).
    ``k_scale``/``v_scale`` [n_blocks, bs, KV] ride as two extra operands
    whose index map is the same block-table lookup; the kernel
    dequantizes in-register.  ``interpret=None`` compiles on a TPU backend
    and interprets elsewhere."""
    B, S, H, hd = q.shape
    n_blocks, bs, KV, _ = k_arena.shape
    nb = block_tables.shape[1]
    quantized = k_scale is not None

    st = _q_tile(S, H)
    kern = functools.partial(_paged_attn_kernel, bs=bs, nb=nb,
                             n_rep=H // KV, window=window,
                             quantized=quantized)
    q_spec = pl.BlockSpec((1, st, H, hd),
                          lambda b, i, j, bt, cu: (b, i, 0, 0))
    kv_spec = pl.BlockSpec((1, bs, KV, hd),
                           lambda b, i, j, bt, cu: (bt[b, j], 0, 0, 0))
    sc_spec = pl.BlockSpec((1, bs, KV),
                           lambda b, i, j, bt, cu: (bt[b, j], 0, 0))

    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k_arena, v_arena]
    if quantized:
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # block tables, cursors
        grid=(B, S // st, nb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((H, st), jnp.float32),      # running max
            pltpu.VMEM((H, st), jnp.float32),      # running sum
            pltpu.VMEM((H, st, hd), jnp.float32),  # weighted-value acc
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables.astype(jnp.int32), cursor.astype(jnp.int32),
      *operands)
    return out


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

def paged_attention(q, k_arena, v_arena, block_tables, cursor, *,
                    window: int | None = None,
                    backend: str | None = None,
                    k_scale=None, v_scale=None) -> jax.Array:
    backend = backend or _default_backend()
    if backend == "pallas":
        return paged_attention_pallas(
            q, k_arena, v_arena, block_tables, cursor, window=window,
            k_scale=k_scale, v_scale=v_scale)
    return paged_attention_ref(q, k_arena, v_arena, block_tables, cursor,
                               window=window, k_scale=k_scale,
                               v_scale=v_scale)
