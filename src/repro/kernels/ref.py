"""Pure-jnp oracles for the sparse-linear kernel.

The ground truth the Pallas kernel is validated against (tests/test_kernels.py
sweeps shapes and dtypes with assert_allclose), written from the unpacked
indices so they share no decoding with the kernel.  The served portable path
is ``models/sparse_serving.sparse_apply_portable``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def decompress_nm(values: jax.Array, indices: jax.Array, m: int,
                  dtype=None) -> jax.Array:
    """[out, nb*n], [out, nb, n] int32 -> dense [out, nb*m].

    Position semantics: ``indices[o, b, k]`` is the column offset inside block
    ``b`` (0..m-1) of value ``values[o, b*n + k]``.
    """
    out, nb, n = indices.shape
    vals = values.reshape(out, nb, n).astype(dtype or values.dtype)
    onehot = jax.nn.one_hot(indices, m, dtype=vals.dtype)       # [out, nb, n, m]
    dense = jnp.einsum("obn,obnm->obm", vals, onehot)
    return dense.reshape(out, nb * m)


def nm_spmm_ref(x: jax.Array, values: jax.Array, indices: jax.Array,
                m: int) -> jax.Array:
    """y = x @ W^T with W the N:M-compressed matrix. x: [b, in]."""
    w = decompress_nm(values, indices, m, dtype=jnp.float32)
    return (x.astype(jnp.float32) @ w.T).astype(x.dtype)


def outlier_spmm_ref(x: jax.Array, values: jax.Array, indices: jax.Array,
                     m: int = 256) -> jax.Array:
    """y = x @ O^T with O the N:256 structured outlier matrix.

    values/indices: [out, in//m, n].
    """
    out, nb, n = values.shape
    w = decompress_nm(values.reshape(out, nb * n), indices, m, dtype=jnp.float32)
    return (x.astype(jnp.float32) @ w.T).astype(x.dtype)


def fused_sparse_linear_ref(x: jax.Array,
                            nm_values: jax.Array, nm_indices: jax.Array, nm_m: int,
                            o_values: jax.Array | None, o_indices: jax.Array | None,
                            o_m: int = 256) -> jax.Array:
    """y = x @ (W_nm + O)^T — the production path.

    By construction (core/pipeline.py) W_nm holds exact zeros at salient
    positions, so plain addition never double-counts.
    """
    w = decompress_nm(nm_values, nm_indices, nm_m, dtype=jnp.float32)
    if o_values is not None:
        out, nb, n = o_values.shape
        w = w + decompress_nm(o_values.reshape(out, nb * n), o_indices, o_m,
                              dtype=jnp.float32)
    return (x.astype(jnp.float32) @ w.T).astype(x.dtype)
