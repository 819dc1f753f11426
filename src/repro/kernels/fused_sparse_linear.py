"""Pallas TPU kernel: fused N:M + structured-outlier linear — the production
serving path.

y = x @ (W_nm + O)^T in ONE pass: both compressed streams are decompressed
into the same VMEM tile and hit the MXU once, so x is read once and y written
once (vs 2x for nm_spmm + outlier_spmm).  W_nm carries exact zeros at salient
slots (core/pipeline.py), so the sum is exact.

HBM bytes per weight tile (bf16, 8:16 + 16:256):
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
from .nm_spmm import _decompress_tile_t, k_tile
from .outlier_spmm import OUTLIER_M, _decompress_outlier_tile_t


def _kernel(x_ref, v_ref, meta_ref, ov_ref, ometa_ref, *refs, n, m, o_n, n_k):
    """One (b, out, k) grid step.  An int8 variant passes a [1, bO] per-
    out-row scale that dequantizes the N:M tile in-register; outliers stay
    exact bf16 and are added AFTER the scale — only the N:M stream is
    quantized (models/sparse_serving.py keeps outliers exact)."""
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
    w += _decompress_outlier_tile_t(ov_ref, ometa_ref, o_n)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "m", "o_n", "block_b",
                                             "block_o", "block_k", "interpret"))
def fused_sparse_linear(x: jax.Array, nm_values: jax.Array, nm_meta: jax.Array,
                        o_values: jax.Array, o_meta: jax.Array, *,
                        n: int, m: int, o_n: int,
                        scale: jax.Array | None = None,
                        block_b: int = 128, block_o: int = 128,
                        block_k: int | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """x: [b, in]; nm_values: [out, in*n//m]; nm_meta: [out, in//m] int32;
    o_values: [out, in//256, o_n]; o_meta: [out, in//256, o_n//4] int32.
    ``scale`` [out] f32 dequantizes int8 nm_values in-register (outliers are
    stored exact and added unscaled); None for bf16 values.  Tiles and
    ``interpret`` default as in ``nm_spmm``."""
    b, kdim = x.shape
    out = nm_values.shape[0]
    assert kdim % OUTLIER_M == 0 and kdim % m == 0

    bb = min(block_b, b)
    bo = min(block_o, out)
    bk = max(k_tile(kdim, block_k), OUTLIER_M)
    assert b % bb == 0 and out % bo == 0 and kdim % bk == 0 and bk % OUTLIER_M == 0
    n_k = kdim // bk
    nc = bk // OUTLIER_M

    grid = (b // bb, out // bo, n_k)
    in_specs = [
        pl.BlockSpec((bb, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bo, bk // m * n), lambda i, j, k: (j, k)),
        pl.BlockSpec((bo, bk // m), lambda i, j, k: (j, k)),
        pl.BlockSpec((bo, nc, o_n), lambda i, j, k: (j, k, 0)),
        pl.BlockSpec((bo, nc, o_n // 4), lambda i, j, k: (j, k, 0)),
    ]
    operands = [x, nm_values, nm_meta, o_values, o_meta]
    if scale is not None:
        assert scale.shape == (out,)
        in_specs.append(pl.BlockSpec((1, bo), lambda i, j, k: (0, j)))
        operands.append(scale.astype(jnp.float32).reshape(1, out))
    return pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, o_n=o_n, n_k=n_k),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bo), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bb, bo), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(*operands)
