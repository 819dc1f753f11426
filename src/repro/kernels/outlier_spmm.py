"""Pallas TPU kernel: structured N:256 salient-weight ("outlier") matmul.

SSP-for-SW (paper contribution 2) on TPU: each 256-wide input block of a row
holds exactly N outliers (N in {4, 8, 16}).  A 256-block spans two 128-lane
registers, so decompress-to-tile keeps accesses perfectly regular — the
paper's hardware-efficiency argument, realized on the MXU.

Layout:
  values : [out, in/256, n]            exact salient values
  meta   : [out, in/256, n/4] int32    indices packed 8 bits x4 per word

Grid and accumulation mirror nm_spmm; typically fused (see
fused_sparse_linear.py) — the standalone kernel exists for composability and
for the unstructured-vs-structured benchmark.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret
from .nm_spmm import k_tile

OUTLIER_M = 256


def pack_outlier_meta(indices: jax.Array) -> jax.Array:
    """[out, nb, n] int32 (0..255) -> [out, nb, n//4] int32, 8 bits each."""
    out, nb, n = indices.shape
    assert n % 4 == 0
    grouped = indices.reshape(out, nb, n // 4, 4)
    shifts = 8 * jnp.arange(4, dtype=jnp.int32)
    return jnp.sum(grouped << shifts[None, None, None, :], axis=-1).astype(jnp.int32)


def unpack_outlier_meta(meta: jax.Array, n: int) -> jax.Array:
    """[out, nb, n//4] int32 -> [out, nb, n] int32."""
    shifts = 8 * jnp.arange(4, dtype=jnp.int32)
    idx = (meta[..., None] >> shifts) & 0xFF
    return idx.reshape(*meta.shape[:-1], n)


def _decompress_outlier_tile_t(v_ref, meta_ref, n: int):
    """values [bO, nc, n], meta [bO, nc, n//4] refs -> dense^T [nc*256, bO]
    f32, built transposed like ``nm_spmm._decompress_tile_t``: one
    256-row group per outlier block, each kept value broadcast down its
    group's sublanes and selected where its 8-bit index matches the row."""
    nc = v_ref.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (OUTLIER_M, 1), 0)
    groups = []
    for c in range(nc):
        vals = v_ref[:, c, :].astype(jnp.float32).T             # [n, bO]
        meta = meta_ref[:, c, :].T                              # [n//4, bO]
        dense = jnp.zeros((OUTLIER_M, vals.shape[1]), jnp.float32)
        for k in range(n):
            idx = (meta[k // 4:k // 4 + 1, :] >> (8 * (k % 4))) & 0xFF
            dense += jnp.where(idx == rows, vals[k:k + 1, :], 0.0)
        groups.append(dense)
    return jnp.concatenate(groups, axis=0)


def _kernel(x_ref, v_ref, meta_ref, o_ref, acc_ref, *, n, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decompress_outlier_tile_t(v_ref, meta_ref, n)           # [bK, bO]
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "block_b", "block_o",
                                             "block_k", "interpret"))
def outlier_spmm(x: jax.Array, values: jax.Array, meta: jax.Array, *,
                 n: int, block_b: int = 128, block_o: int = 128,
                 block_k: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """y[b, out] = x[b, in] @ decompress_outliers^T."""
    b, kdim = x.shape
    out, nb, npk = values.shape[0], values.shape[1], meta.shape[2]
    assert kdim == nb * OUTLIER_M and npk == n // 4

    bb = min(block_b, b)
    bo = min(block_o, out)
    bk = max(k_tile(kdim, block_k), OUTLIER_M)
    assert b % bb == 0 and out % bo == 0 and kdim % bk == 0 and bk % OUTLIER_M == 0
    n_k = kdim // bk
    nc = bk // OUTLIER_M

    grid = (b // bb, out // bo, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n=n, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bo, nc, n), lambda i, j, k: (j, k, 0)),
            pl.BlockSpec((bo, nc, n // 4), lambda i, j, k: (j, k, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bo), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bb, bo), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, values, meta)
