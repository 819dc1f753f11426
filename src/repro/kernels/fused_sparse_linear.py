"""Pallas TPU kernel: fused N:M + structured-outlier linear — the served
sparse linear on a TPU (``models/sparse_serving.sparse_apply``).

y = x @ (W_nm + O)^T in ONE pass: both compressed streams are decompressed
into the same VMEM tile and hit the MXU once, so x is read once and y written
once.  W_nm carries exact zeros at salient slots (core/pipeline.py) and the
kept positions of a block are distinct, so writing each value into its slot
(a select that replaces, never an add) gives the exact weight.

Grid: (out tiles, k tiles, row tiles), rows innermost.  Each weight tile is
decompressed into VMEM on the first row tile only and reused by every other,
so a call pays one decompression per weight element whatever its row count;
partial sums over k tiles are kept in an f32 accumulator for all of the
call's rows.  The tile is built transposed (the output dim on the lanes),
one pass over 8 m-blocks at a time, all m positions of the pass at once:

  idx[k, c, o]      = (meta[o, c] >> 4k) & 0xF                 # unpack
  wT[c*m + j, o]    = values[o, c*n + k]  where idx == j        # VPU selects
  wT[256g + p, o]   = outlier[o, g, t]    where its index == p
  acc[r, o]        += x[r, :] @ wT[:, o]                        # MXU

The n kept values of a block are read from a transposed copy with a
sublane stride of n, and each of the m positions of a block is written with
a sublane stride of m, so no value is ever broadcast or reshaped across
lanes.  The loops over kept values, outliers and positions are
``fori_loop``s over whole-array operations, never Python unrolls, so the
traced kernel has the same hundred-odd equations at any o_n and tile
(see ``_decompress``).  The tile is cast once to the dtype of x for the MXU
(bf16 when serving), with f32 accumulation: the weight is exact in bf16
because the values are stored bf16; an int8 value is scaled in f32 and
rounded to bf16 once, as the portable path rounds it.

HBM bytes per weight element (bf16, 8:16 + 16:256):
  dense:              2.000 B/elem
  fused compressed:   0.5*2 (values) + 4b idx/16-block (0.25)
                      + 0.0625*2 (outlier vals) + 0.0625 (outlier meta 8b)
                    = 1.4375 B/elem -> 1.39x weight-traffic reduction.
  (The paper's 0.875 bits/elem metadata assumes enumerative decoding in
  silicon; the software-decodable 4-bit index layout spends 2 bits/elem.
  With such hardware the ratio improves to 1.30 B/elem = 1.54x.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

OUTLIER_M = 256                    # width of an outlier group (N:256)
LANES = 128
SUBLANES = 8
VMEM_LIMIT = 96 * 1024 * 1024      # of v5e's 128 MiB; 4096 rows need <= 22
K_TILE = 2048


def k_tile(kdim: int, block_k: int | None) -> int:
    """Contraction tile: ``block_k`` when given, else ``K_TILE`` where it
    divides ``kdim`` and the whole of ``kdim`` otherwise.  At 2048 every
    metadata block is lane-aligned on the TPU: in/m = 128 lanes for 8:16,
    and eight 256-wide outlier groups on the sublanes.  No narrower tile
    is: a metadata block's in/m lanes must be a multiple of 128 or the
    whole row, so a ``kdim`` that 2048 does not divide (LLaMA-2's 11008,
    13824 and 5120) is taken whole."""
    if block_k is not None:
        return min(block_k, kdim)
    return K_TILE if kdim % K_TILE == 0 else kdim


def _decompress(v_ref, meta_ref, ov_ref, om_ref, s_ref, w_ref, vstage,
                mstage, ostage, istage, wstage, wrows, *, n, m):
    """Write the dense^T [bK, bO] tile of one (out, k) block into ``w_ref``
    in its dtype, one 128-lane slab of the out dim at a time (a strided
    access needs a base whose last dim is 128).  Per slab the operands are
    staged transposed: ``vstage``/``mstage`` the values (scaled, f32) and
    metadata, ``ostage``/``istage`` the outliers' values and packed
    indices.  Each pass over 8 m-blocks (one per sublane) then builds all m
    positions at once, ``w[j, c, o]``, by one select per kept value and per
    outlier, and stores position j with a sublane stride of m.

    Every loop is a ``fori_loop`` over whole-array ``lax`` operations, so
    the traced body has the same size whatever n, m, o_n and the tiles.
    Each step program traces and lowers the kernel once per projection, in
    every process, before the compile cache is consulted, so the body's
    size is paid in every set-up; ``jnp`` would add a nested jit per
    operator."""
    lax = jax.lax
    nc, lanes = mstage.shape
    rows = wrows.shape[1]
    shape = wrows.shape                                    # [m, rows, lanes]
    f32, i32 = jnp.float32, jnp.int32
    pos = lax.broadcasted_iota(i32, shape, 0)

    def spread(v):                     # [1 or rows, lanes] -> [m, rows, lanes]
        return lax.broadcast_in_dim(v, shape, (1, 2))

    def slab(a, carry):
        ln = pl.ds(pl.multiple_of(lax.mul(a, lanes), lanes), lanes)
        vals = lax.transpose(lax.convert_element_type(v_ref[ln, :], f32),
                             (1, 0))                         # [nc*n, L]
        if s_ref is not None:                                 # int8 values
            vals = lax.mul(vals, lax.broadcast_in_dim(s_ref[:, ln],
                                                      vals.shape, (0, 1)))
        vstage[...] = vals
        mstage[...] = lax.transpose(meta_ref[ln, :], (1, 0))  # [nc, L]
        if ov_ref is not None:
            ostage[...] = lax.convert_element_type(ov_ref[:, :, ln], f32)
            istage[...] = om_ref[:, :, ln]                    # [ng, o_n/4, L]
        lax.fori_loop(0, nc // rows, block_rows, 0)
        w_ref[:, ln] = lax.convert_element_type(wstage[...], w_ref.dtype)
        return carry

    def block_rows(q, carry):
        c0 = pl.multiple_of(lax.mul(q, rows), rows)

        def kept(k, wm):                 # the block's k-th value at its index
            w, meta = wm
            val = vstage[pl.ds(lax.add(lax.mul(c0, n), k), rows, stride=n), :]
            w = lax.select(lax.eq(spread(lax.bitwise_and(meta, 0xF)), pos),
                           spread(val), w)
            return w, lax.shift_right_logical(meta, 4)

        w, _ = lax.fori_loop(0, n, kept, (lax.full(shape, 0.0, f32),
                                          mstage[pl.ds(c0, rows), :]))
        if ov_ref is not None:
            per_group = OUTLIER_M // m   # m-blocks per outlier group
            g = lax.div(c0, per_group)
            # each sublane's position in its 256-wide group
            at = lax.add(pos, spread(lax.mul(lax.add(
                lax.rem(c0, per_group),
                lax.broadcasted_iota(i32, (rows, lanes), 0)), m)))

            def outliers4(u, w):         # the 4 outliers of index word u
                word = istage[g, pl.ds(u, 1), :]
                for b in range(4):
                    idx = lax.bitwise_and(lax.shift_right_logical(word, 8 * b),
                                          0xFF)
                    val = ostage[g, pl.ds(lax.add(lax.mul(u, 4), b), 1), :]
                    w = lax.select(lax.eq(spread(idx), at), spread(val), w)
                return w

            w = lax.fori_loop(0, istage.shape[1], outliers4, w)
        wrows[...] = w

        def put(j, carry):
            start = lax.add(lax.mul(c0, m), j)
            wstage[pl.ds(start, rows, stride=m), :] = wrows[j]
            return carry

        return lax.fori_loop(0, m, put, carry)

    lax.fori_loop(0, w_ref.shape[1] // lanes, slab, 0)


def _kernel(*refs, n, m, n_k, outliers, scaled):
    lax = jax.lax
    refs = list(refs)
    x_ref, v_ref, meta_ref = refs[:3]
    del refs[:3]
    ov_ref = om_ref = s_ref = None
    if outliers:
        ov_ref, om_ref = refs[:2]
        del refs[:2]
    if scaled:
        s_ref = refs.pop(0)
    (o_ref, w_ref, vstage, mstage, wstage, wrows, *rest) = refs
    ostage, istage = rest[:2] if outliers else (None, None)
    k, i = pl.program_id(1), pl.program_id(2)

    @pl.when(lax.eq(i, 0))
    def _unpack():
        _decompress(v_ref, meta_ref, ov_ref, om_ref, s_ref, w_ref, vstage,
                    mstage, ostage, istage, wstage, wrows, n=n, m=m)

    br = x_ref.shape[0]
    r = pl.ds(pl.multiple_of(lax.mul(i, br), br), br)
    y = lax.dot_general(x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    if n_k > 1:                          # accumulate across k tiles
        acc_ref = rest[-1]
        y = lax.select(lax.gt(k, 0), lax.add(acc_ref[r, :], y), y)
        acc_ref[r, :] = y
    # the output block stays in VMEM across k and is written back after
    # the last k tile, so a partial sum stored here is overwritten
    o_ref[r, :] = lax.convert_element_type(y, o_ref.dtype)


# jitted, so a step program traces and lowers the kernel once per distinct
# projection shape (wq and wo, wk and wv, w_gate and w_up share one)
@functools.partial(jax.jit, static_argnames=("n", "m", "o_n", "block_b",
                                             "block_o", "block_k", "interpret"))
def fused_sparse_linear(x: jax.Array, nm_values: jax.Array, nm_meta: jax.Array,
                        o_values: jax.Array | None = None,
                        o_meta: jax.Array | None = None, *,
                        n: int, m: int, o_n: int = 0,
                        scale: jax.Array | None = None,
                        block_b: int = 512, block_o: int = 256,
                        block_k: int | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """x: [rows, in]; nm_values: [out, in*n//m]; nm_meta: [out, in//m]
    int32; o_values: [out, in//256, o_n]; o_meta: [out, in//256, o_n//4]
    int32, or both None for a weight without outliers.  ``scale`` [out] f32
    dequantizes int8 nm_values (outliers are stored exact and unscaled);
    None for bf16 values.  The MXU takes x and the tile in x's dtype.

    Tiles are clamped to the array sizes; ``block_k=None`` picks the
    TPU-legal ``k_tile``, and a k tile wider than ``K_TILE`` narrows the
    out and row tiles.  Rows are padded up to a whole row tile.
    ``interpret=None`` compiles on a TPU backend and interprets elsewhere.
    """
    rows, kdim = x.shape
    out = nm_values.shape[0]
    outliers = o_values is not None
    assert kdim % m == 0 and nm_values.shape[1] == kdim // m * n
    assert nm_meta.shape == (out, kdim // m)

    bo = min(block_o, out)
    bk = k_tile(kdim, block_k)
    if outliers:
        bk = max(bk, OUTLIER_M)
        assert kdim % OUTLIER_M == 0 and bk % OUTLIER_M == 0 and o_n % 4 == 0
    if bk > K_TILE:
        # a contraction dim taken whole (``k_tile``): narrow the out and row
        # tiles in proportion, so VMEM holds about what a K_TILE-wide tile
        # holds (LLaMA-2-13B's w_down at 4096 rows needs 86 MiB otherwise)
        bo = min(bo, LANES)
        block_b = max(SUBLANES,
                      block_b * K_TILE // bk // SUBLANES * SUBLANES)
    nc = bk // m
    assert out % bo == 0 and bo % min(LANES, bo) == 0 and kdim % bk == 0
    assert nc % min(SUBLANES, nc) == 0
    br = min(block_b, -(-rows // SUBLANES) * SUBLANES)
    padded = -(-rows // br) * br
    if padded != rows:
        x = jax.lax.pad(x, jnp.zeros((), x.dtype),
                        ((0, padded - rows, 0), (0, 0, 0)))
    n_k = kdim // bk

    grid = (out // bo, n_k, padded // br)
    in_specs = [
        pl.BlockSpec((br, bk), lambda j, k, i: (i, k)),
        pl.BlockSpec((bo, nc * n), lambda j, k, i: (j, k)),
        pl.BlockSpec((bo, nc), lambda j, k, i: (j, k)),
    ]
    operands = [x, nm_values, nm_meta]
    lanes = min(LANES, bo)
    slabs = bo // lanes
    scratch = [pltpu.VMEM((bk, bo), x.dtype),                   # MXU tile
               pltpu.VMEM((nc * n, lanes), jnp.float32),        # values^T
               pltpu.VMEM((nc, lanes), jnp.int32),              # meta^T
               pltpu.VMEM((bk, lanes), jnp.float32),            # dense^T
               pltpu.VMEM((m, min(SUBLANES, nc), lanes), jnp.float32)]
    if outliers:
        ng = bk // OUTLIER_M
        in_specs += [pl.BlockSpec((ng, o_n, bo), lambda j, k, i: (k, 0, j)),
                     pl.BlockSpec((ng, o_n // 4, bo),
                                  lambda j, k, i: (k, 0, j))]
        # out on the lanes: the device keeps these narrow arrays in that
        # order already ([out, in/256, o_n] laid out out-minor), so the
        # transposes are free there
        operands += [jax.lax.transpose(o_values, (1, 2, 0)),
                     jax.lax.transpose(o_meta, (1, 2, 0))]
        scratch += [pltpu.VMEM((ng, o_n, lanes), jnp.float32),
                    pltpu.VMEM((ng, o_n // 4, lanes), jnp.int32)]
    if scale is not None:
        assert scale.shape == (out,)
        in_specs.append(pl.BlockSpec((1, bo), lambda j, k, i: (0, j)))
        operands.append(jax.lax.reshape(
            jax.lax.convert_element_type(scale, jnp.float32), (1, out)))
    if n_k > 1:
        scratch.append(pltpu.VMEM((padded, bo), jnp.float32))  # accumulator
    y = pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, n_k=n_k, outliers=outliers,
                          scaled=scale is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((padded, bo), lambda j, k, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((padded, out), x.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
        name="sparse_linear",
    )(*operands)
    return jax.lax.slice(y, (0, 0), (rows, out)) if padded != rows else y
