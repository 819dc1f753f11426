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
  ``[B, nb*block_size, KV, hd]`` view with ``arena[layer, block_tables]``
  and run the masked softmax.  A table maps sequence position ``p`` to gathered
  index ``p`` exactly, so the mask carries over unchanged — XLA fuses the
  gather, so this is also the portable CPU/GPU path.
- ``pallas``: a TPU kernel (interpret-mode fallback off-TPU) that never
  materializes the gathered view.  The block tables, cursors and layer
  index ride in as scalar-prefetch operands and the whole [L, ...] arena
  stays in HBM: the kernel reads ``arena[layer, block]``, so the layer
  loop never slices a layer out of it.  The grid is
  ``(B, query tiles)``; each grid step walks only the table blocks its
  query tile can see (``visible_blocks``: up to its last query's
  position, from its first query's window start), ``P`` blocks per walk
  step (``kv_walk``: 256 tokens at decode, fewer for large chunk tiles),
  each block DMA'd by table lookup into a double-buffered VMEM scratch
  while the previous step computes, the next tile's first step included.
  Query heads are grouped by their KV head against K and V as stored, and
  the tile accumulates flash-style (running max / sum / weighted value in
  VMEM scratch).  Query tiles (``_q_tile``) cap the q/out/accumulator
  tiles at ``_Q_ROWS`` query-head rows, so every model width and chunk
  size fits scoped VMEM with the same kernel.  Both the kernel's work and
  its HBM traffic are therefore proportional to the tokens each query
  tile can see, not to the table width or a reserved ``max_len``: a
  decode call over rows of about 800 tokens in 2560-token tables walks
  4 steps per row.  ``kv_steps`` counts the walk on the host.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...kernels import resolve_interpret

_NEG_INF = -1e30

# query x q-head rows of one grid step's q/out/accumulator tiles: 4096
# rows of hd=128 take about 20 MiB of scoped VMEM on a v5e with their
# running max and sum, double-buffered where they are pipelined
_Q_ROWS = 4096

# KV tokens one walk step covers at most (16 blocks of 16), and the VMEM
# a step's KV buffers, their f32 upcasts and its f32 scores may take
# (``kv_walk``): 256 tokens at decode, 128 for a 4096-row chunk tile
_STEP_TOKENS = 256
_STEP_VMEM = 8 << 20
# the scoped VMEM the kernel may use (a v5e core has 128 MiB)
_VMEM_LIMIT = 32 << 20


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
                        *, layer, window: int | None = None,
                        k_scale=None, v_scale=None) -> jax.Array:
    """q [B,S,H,hd]; arenas [L, n_blocks, bs, KV, hd], of which layer
    ``layer`` is read; block_tables [B, nb] int32; cursor [B] (tokens
    visible per row before this step's S fresh ones).  Returns
    [B,S,H,hd].

    A table maps sequence position ``p`` to gathered index ``p`` exactly,
    so after the gather this IS the contiguous length-masked attention —
    delegated to ``models/layers.attend_length_masked`` so the masking
    rule lives in one place.  The gather reads the layer's blocks from
    the whole arena, so no layer is sliced out first.
    ``k_scale``/``v_scale`` [L, n_blocks, bs, KV] are an int8 arena's
    per-position scales, gathered through the same tables (XLA fuses
    gather + dequant — no bf16 arena copy)."""
    from ...models.layers import attend_length_masked
    B, S, H, hd = q.shape
    _, _, bs, KV, _ = k_arena.shape
    nb = block_tables.shape[1]
    k = k_arena[layer, block_tables].reshape(B, nb * bs, KV, hd)
    v = v_arena[layer, block_tables].reshape(B, nb * bs, KV, hd)
    ks = vs = None
    if k_scale is not None:
        ks = k_scale[layer, block_tables].reshape(B, nb * bs, KV)
        vs = v_scale[layer, block_tables].reshape(B, nb * bs, KV)
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


def kv_walk(S: int, H: int, KV: int, hd: int, bs: int, nb: int,
            itemsize: int) -> tuple[int, int]:
    """(query tile ``st``, table blocks per walk step ``P``) of the kernel
    for a [.., S, H, hd] query over a ``KV``-head arena of ``bs``-token
    blocks of ``itemsize``-byte elements, in tables ``nb`` wide.  A step
    covers up to ``_STEP_TOKENS`` tokens, halved while its VMEM (the
    double-buffered K and V blocks, their f32 upcasts, and the tile's f32
    scores and probabilities) would pass ``_STEP_VMEM``."""
    st = _q_tile(S, H)
    per_token = 4 * KV * hd * itemsize + 2 * KV * hd * 4 + 2 * st * H * 4
    P = max(min(_STEP_TOKENS // bs, nb), 1)
    while P > 1 and P * bs * per_token > _STEP_VMEM:
        P //= 2
    return st, P


def visible_blocks(cursor, qi, st: int, bs: int, nb: int,
                   window: int | None, xp=np):
    """First and last table block (inclusive) that query tile ``qi`` of a
    row at ``cursor`` can see: its queries sit at ``cursor + qi*st ..
    cursor + qi*st + st - 1`` and see positions up to their own,
    window-limited.  ``xp`` is numpy on the host, ``jax.numpy`` inside the
    kernel; both sides walk exactly these bounds.  Both stay inside the
    table whatever the cursor, so no lookup leaves the row's entries."""
    last = xp.clip((cursor + (qi + 1) * st - 1) // bs, 0, nb - 1)
    if window is None:
        return last * 0, last
    first = xp.maximum(cursor + qi * st - window + 1, 0) // bs
    return xp.minimum(first, last), last


def kv_steps(cursor, S: int, H: int, KV: int, hd: int, bs: int, nb: int,
             itemsize: int, window: int | None) -> tuple[int, int]:
    """(walked, grid) KV steps of one kernel call over rows at ``cursor``
    [B] (host numpy): the steps the kernel walks, and the
    ``B x query tiles x ceil(nb / P)`` it would walk with no bound."""
    st, P = kv_walk(S, H, KV, hd, bs, nb, itemsize)
    cursor = np.asarray(cursor, np.int64)[:, None]
    qi = np.arange(S // st)[None, :]
    first, last = visible_blocks(cursor, qi, st, bs, nb, window)
    walked = int(np.sum(last // P - first // P + 1))
    return walked, cursor.shape[0] * (S // st) * -(-nb // P)


def _scores(q, k_ref):
    """[KV, R, T] f32 scores of q [KV, R, hd] against the [P, bs, KV, hd]
    keys in ``k_ref``, each query head against its own KV head.  int8
    keys are exact in bf16 (their scales multiply the scores); keys and
    queries meet the MXU in their stored dtype when the two agree, else
    in f32."""
    k = k_ref[...]
    k = jnp.swapaxes(k.reshape(-1, *k.shape[2:]), 0, 1)       # [KV, T, hd]
    if k.dtype == jnp.int8:
        k = k.astype(jnp.bfloat16)
    if k.dtype != q.dtype:
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    return jnp.einsum("grd,gtd->grt", q, k,
                      preferred_element_type=jnp.float32)


def _weighted(p, v_ref):
    """[KV, R, hd] f32: probabilities p [KV, R, T] times the [P, bs, KV,
    hd] values in ``v_ref``, in f32."""
    v = v_ref[...]
    v = jnp.swapaxes(v.reshape(-1, *v.shape[2:]), 0, 1)       # [KV, T, hd]
    return jnp.einsum("grt,gtd->grd", p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _paged_attn_kernel(bt_ref, cur_ref, layer_ref, q_ref, k_hbm, v_hbm,
                       *refs, bs, nb, P, st, n_rep, window, quantized,
                       scale):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf,
         ks_buf, vs_buf, sems, slot_ref) = refs
        hbm = (k_hbm, v_hbm, ks_hbm, vs_hbm)
        bufs = (k_buf, v_buf, ks_buf, vs_buf)
    else:
        o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sems, slot_ref = refs
        hbm, bufs = (k_hbm, v_hbm), (k_buf, v_buf)
    b, qi = pl.program_id(0), pl.program_id(1)
    n_rows, n_qt = pl.num_programs(0), pl.num_programs(1)
    T = P * bs
    KV = k_buf.shape[3]
    layer = layer_ref[0]

    def bounds(b, qi):
        return visible_blocks(cur_ref[b], qi, st, bs, nb, window, jnp)

    def dmas(b, qi, step, slot, method):
        """Start or wait for the DMAs of walk step ``step`` of tile (b,
        qi) into ``slot``: one per array for each of its P table blocks
        that the tile can see, from the layer's part of the value arenas
        (the scale rows hold one layer only).  A block it cannot see is
        never read."""
        first, last = bounds(b, qi)

        def block(p, carry):
            j = step * P + p
            blk = bt_ref[b * nb + jnp.minimum(j, nb - 1)]

            @pl.when((j >= first) & (j <= last))
            def _():
                for a, (src, buf) in enumerate(zip(hbm, bufs)):
                    at = src.at[layer, blk] if a < 2 else src.at[blk]
                    getattr(pltpu.make_async_copy(
                        at, buf.at[slot, p], sems.at[a, slot]), method)()
            return carry

        jax.lax.fori_loop(0, P, block, 0)

    first, last = bounds(b, qi)
    s0, s1 = first // P, last // P

    @pl.when((b == 0) & (qi == 0))
    def _prologue():
        # a block a step does not fetch keeps what its buffer held: zeros
        # or KV that some tile could see, finite either way, so the masked
        # probabilities (exactly 0) never multiply a NaN
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        dmas(b, qi, s0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                              # [KV, R, hd]
    R = q.shape[1]
    # row r of a KV head's group is query r // n_rep of the tile
    qpos = (cur_ref[b] + qi * st
            + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // n_rep)
    # the tile after this one in grid order, whose first step is in
    # flight during this tile's last
    nxt_b = jnp.where(qi + 1 < n_qt, b, b + 1)
    nxt_qi = jnp.where(qi + 1 < n_qt, qi + 1, 0)
    nxt_s0 = bounds(jnp.minimum(nxt_b, n_rows - 1), nxt_qi)[0] // P

    def step_scales(buf, slot):
        """[KV, T] scales of the step's tokens from its P rows of
        ``bs * KV`` (lane-padded) scales, token-major: each row repeats
        for its bs tokens, each token keeps its own KV lanes, and a 0/1
        matmul moves them into place.  The MXU takes bf16, so the f32
        scales go as three bf16 parts, which sum back exactly."""
        W = buf.shape[-1]
        rows = jnp.broadcast_to(buf[slot][:, None, :], (P, bs, W))
        rows = rows.reshape(T, W)                             # [T, W]
        lane = jax.lax.broadcasted_iota(jnp.int32, (T, W), 1)
        t = jax.lax.broadcasted_iota(jnp.int32, (T, W), 0) % bs
        rows = jnp.where(lane // KV == t, rows, 0.0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (KV, W), 1)
        g = jax.lax.broadcasted_iota(jnp.int32, (KV, W), 0)
        pick = jnp.where((lane % KV == g) & (lane < bs * KV), 1.0, 0.0)
        pick = pick.astype(jnp.bfloat16)
        out = 0.0
        for _ in range(3):
            part = rows.astype(jnp.bfloat16)
            rows = rows - part.astype(jnp.float32)
            out = out + jnp.einsum("gl,tl->gt", pick, part,
                                   preferred_element_type=jnp.float32)
        return out

    def body(step, slot):
        @pl.when((step < s1) | (nxt_b < n_rows))
        def _():
            more = step < s1
            dmas(jnp.where(more, b, nxt_b), jnp.where(more, qi, nxt_qi),
                 jnp.where(more, step + 1, nxt_s0), 1 - slot, "start")

        dmas(b, qi, step, slot, "wait")
        s = _scores(q, k_buf.at[slot]) * scale                # [KV, R, T]
        if quantized:
            s = s * step_scales(ks_buf, slot)[:, None, :]
        tok = step * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        valid = tok <= qpos                                   # [R, T]
        if window is not None:
            valid &= tok > qpos - window
        if nb % P:
            valid &= tok < nb * bs          # the last step runs past the table
        s = jnp.where(valid[None], s, _NEG_INF)

        m_prev = m_ref[...]                                   # [KV, R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        if quantized:
            p = p * step_scales(vs_buf, slot)[:, None, :]
        acc_ref[...] = acc_ref[...] * alpha + _weighted(p, v_buf.at[slot])
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(s0, s1 + 1, body, slot_ref[0])
    denom = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_pallas(q, k_arena, v_arena, block_tables, cursor,
                           *, layer, window: int | None = None,
                           interpret: bool | None = None,
                           k_scale=None, v_scale=None) -> jax.Array:
    """Same contract as ``paged_attention_ref``.  One grid step per (row,
    query tile); each walks only the table blocks the tile can see
    (``visible_blocks``), ``P`` blocks per walk step (``kv_walk``), DMA'd
    by table lookup (``arena[layer, block]``, the layer a scalar-prefetch
    operand beside the tables and cursors) from the whole arena left in
    HBM into a double-buffered VMEM scratch, the next step's copies in
    flight during this step's compute.  Query heads are grouped by their
    KV head, so K and V are never repeated.  ``k_scale``/``v_scale`` [L,
    n_blocks, bs, KV] come by the same lookups, one lane-dense row per
    block of the layer; the kernel dequantizes in-register.
    ``interpret=None`` compiles on a TPU backend and interprets
    elsewhere."""
    B, S, H, hd = q.shape
    _, n_blocks, bs, KV, _ = k_arena.shape
    nb = block_tables.shape[1]
    n_rep = H // KV
    quantized = k_scale is not None

    st, P = kv_walk(S, H, KV, hd, bs, nb, k_arena.dtype.itemsize)
    R = st * n_rep
    kern = functools.partial(_paged_attn_kernel, bs=bs, nb=nb, P=P, st=st,
                             n_rep=n_rep, window=window, quantized=quantized,
                             scale=1.0 / math.sqrt(hd))
    # [B, KV, S * n_rep, hd]: the query heads of each KV head, query-major
    qg = (q.reshape(B, S, KV, n_rep, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, KV, S * n_rep, hd))
    q_spec = pl.BlockSpec((1, KV, R, hd),
                          lambda b, i, bt, cu, ly: (b, 0, i, 0))
    arenas = [k_arena, v_arena]
    blocks = [a.shape[2:] for a in arenas]
    if quantized:
        # the chip's DMAs slice only lane-dense rows: one row of bs * KV
        # scales per block of this layer, padded to whole lanes
        w = -(-bs * KV // 128) * 128
        arenas += [jnp.pad(s[layer].reshape(n_blocks, bs * KV),
                           ((0, 0), (0, w - bs * KV)))
                   for s in (k_scale, v_scale)]
        blocks += [(w,)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # block tables, cursors, layer
        grid=(B, S // st),
        in_specs=[q_spec] + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(
            arenas),
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, R, 1), jnp.float32),     # running max
            pltpu.VMEM((KV, R, 1), jnp.float32),     # running sum
            pltpu.VMEM((KV, R, hd), jnp.float32),    # weighted-value acc
        ] + [pltpu.VMEM((2, P) + blk, a.dtype)
             for a, blk in zip(arenas, blocks)] + [
            pltpu.SemaphoreType.DMA((len(arenas), 2)),
            pltpu.SMEM((1,), jnp.int32),             # slot of the next step
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(block_tables.astype(jnp.int32).reshape(-1), cursor.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, *arenas)
    return (out.reshape(B, KV, S, n_rep, hd).transpose(0, 2, 1, 3, 4)
            .reshape(B, S, H, hd))


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

def paged_attention(q, k_arena, v_arena, block_tables, cursor, *,
                    layer, window: int | None = None,
                    backend: str | None = None,
                    k_scale=None, v_scale=None) -> jax.Array:
    backend = backend or _default_backend()
    if backend == "pallas":
        return paged_attention_pallas(
            q, k_arena, v_arena, block_tables, cursor, layer=layer,
            window=window, k_scale=k_scale, v_scale=v_scale)
    return paged_attention_ref(q, k_arena, v_arena, block_tables, cursor,
                               layer=layer, window=window, k_scale=k_scale,
                               v_scale=v_scale)
