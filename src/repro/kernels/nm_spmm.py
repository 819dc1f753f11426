"""Pallas TPU kernel: N:M-compressed weight x dense activation matmul.

TPU adaptation of GPU 2:4 sparse tensor cores (see DESIGN.md §3): the MXU has
no sparse mode, so the win is HBM *bandwidth* — weights stream compressed
(values at N/M density + 4-bit packed indices) and are decompressed inside
VMEM by the VPU just before hitting the MXU.

Layout (produced by core/packing.py):
  values : [out, in * n/m]   kept values, row-major by block
  meta   : [out, in/m] int32 per block: n indices packed 4 bits each (m<=16)

Grid: (b_tiles, out_tiles, k_tiles), k innermost; the f32 output tile
accumulates across k.  Decompression per k-tile, built transposed (the
output dim on the lanes, see ``_decompress_tile_t``):

  idx[c, k, o]    = (meta[o, c] >> 4k) & 0xF              # unpack
  wT[c*m + j, o]  = sum_k values[o, c*n + k] * (idx==j)   # compare-select, VPU
  y[b, o]        += x[b, :] @ wT[:, o]                    # MXU

VPU decompress cost is n ops/weight vs 2*B_tile MXU flops/weight, so for
B_tile >= 8 the decompress is not the bottleneck; the kernel exists to halve
weight bytes from HBM.  VMEM per step (defaults bB=bO=128, bK=2048, bf16):
x 512K + vals 256K + meta 64K + w_tile 1M + compare temps ~1M + acc 64K,
double-buffered inputs included well under the 16M scoped default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret


def _decompress_tile_t(values, meta, n: int, m: int):
    """values [bO, nc*n], meta [bO, nc] int32 -> dense^T [nc*m, bO] f32.

    Built transposed, with the output dim on the lanes: the n kept values
    of each m-block then split off along sublanes ([nc*n, bO] ->
    [nc, n, bO]), a relayout Mosaic supports, where the row-major layout
    would need a lane-dim reshape, which it refuses."""
    bo, nc = meta.shape
    vals = values.astype(jnp.float32).T.reshape(nc, n, bo)
    meta_t = meta.T                                            # [nc, bO]
    j = jax.lax.broadcasted_iota(jnp.int32, (1, m, 1), 1)
    dense = jnp.zeros((nc, m, bo), jnp.float32)
    for k in range(n):
        idx = ((meta_t >> (4 * k)) & 0xF)[:, None, :]          # [nc, 1, bO]
        dense += jnp.where(idx == j, vals[:, k:k + 1, :], 0.0)
    return dense.reshape(nc * m, bo)


def k_tile(kdim: int, block_k: int | None) -> int:
    """Contraction tile: ``block_k`` when given, else 2048 where it divides
    ``kdim`` and the whole of ``kdim`` otherwise.  At 2048 every metadata
    block is lane-aligned on the TPU: in/m = 128 lanes for 8:16, and eight
    256-wide outlier groups on the sublanes."""
    if block_k is not None:
        return min(block_k, kdim)
    return 2048 if kdim % 2048 == 0 else kdim


def _kernel(x_ref, v_ref, meta_ref, *refs, n, m, n_k):
    """One (b, out, k) grid step; an int8 variant passes a [1, bO] per-out-
    row scale that dequantizes the decompressed tile in-register."""
    if len(refs) == 3:
        s_ref, o_ref, acc_ref = refs
    else:
        s_ref, (o_ref, acc_ref) = None, refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decompress_tile_t(v_ref[...], meta_ref[...], n, m)   # [bK, bO]
    if s_ref is not None:
        w = w * s_ref[...]
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "m", "block_b", "block_o",
                                             "block_k", "interpret"))
def nm_spmm(x: jax.Array, values: jax.Array, meta: jax.Array, *,
            n: int, m: int, scale: jax.Array | None = None,
            block_b: int = 128, block_o: int = 128,
            block_k: int | None = None,
            interpret: bool | None = None) -> jax.Array:
    """y[b, out] = x[b, in] @ decompress(values, meta)^T.

    x: [batch, in]; values: [out, in*n//m]; meta: [out, in//m] int32.
    ``scale`` [out] f32 dequantizes int8 values in-register after the
    decompress (per-out-row symmetric quantization); None for bf16 values.
    Requires batch % block_b == in % block_k == out % block_o == 0 after
    clamping (tiles are clamped to the array sizes for small shapes);
    ``block_k=None`` picks the TPU-legal ``k_tile``; ``interpret=None``
    compiles on a TPU backend and interprets elsewhere.
    """
    b, kdim = x.shape
    out = values.shape[0]
    assert kdim % m == 0 and values.shape[1] == kdim // m * n
    assert meta.shape == (out, kdim // m)

    bb = min(block_b, b)
    bo = min(block_o, out)
    bk = k_tile(kdim, block_k)
    assert b % bb == 0 and out % bo == 0 and kdim % bk == 0 and bk % m == 0
    n_k = kdim // bk

    grid = (b // bb, out // bo, n_k)
    in_specs = [
        pl.BlockSpec((bb, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bo, bk // m * n), lambda i, j, k: (j, k)),
        pl.BlockSpec((bo, bk // m), lambda i, j, k: (j, k)),
    ]
    operands = [x, values, meta]
    if scale is not None:
        assert scale.shape == (out,)
        in_specs.append(pl.BlockSpec((1, bo), lambda i, j, k: (0, j)))
        operands.append(scale.astype(jnp.float32).reshape(1, out))
    return pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, n_k=n_k),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bo), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bb, bo), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(*operands)
