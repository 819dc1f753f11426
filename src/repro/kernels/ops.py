"""Public jit'd entry points for the sparse kernels.

``sparse_matmul(x, SparsifiedLinear)``-style APIs used by models/serving.
Backend selection:
  - "pallas"     : pl.pallas_call, compiled on a TPU backend and run in
                   interpret mode elsewhere (decided when traced).
  - "reference"  : pure-jnp oracle (ref.py) — portable, used inside pjit'd
                   full-model graphs where the dry-run lowers to HLO (XLA
                   then fuses the decompression einsum itself).

On this CPU container interpret-mode Pallas is slow (Python loop over the
grid), so model-level code defaults to "reference"; kernel correctness is
enforced by the test suite sweeping both paths.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from ..core.packing import PackedNM
from ..core.outliers import StructuredOutliers
from . import ref
from .nm_spmm import nm_spmm
from .outlier_spmm import outlier_spmm, pack_outlier_meta
from .fused_sparse_linear import fused_sparse_linear

_DEFAULT_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND", "reference")


def nm_matmul(x: jax.Array, packed: PackedNM, backend: str | None = None,
              **tiles) -> jax.Array:
    """y = x @ W_nm^T for a PackedNM weight."""
    backend = backend or _DEFAULT_BACKEND
    if backend == "pallas":
        return nm_spmm(x, packed.values, packed.packed_metadata(),
                       n=packed.n, m=packed.m, **tiles)
    return ref.nm_spmm_ref(x, packed.values, packed.indices, packed.m)


def outlier_matmul(x: jax.Array, outliers: StructuredOutliers,
                   backend: str | None = None, **tiles) -> jax.Array:
    """y = x @ O^T for structured N:256 outliers."""
    backend = backend or _DEFAULT_BACKEND
    if backend == "pallas":
        return outlier_spmm(x, outliers.values, pack_outlier_meta(outliers.indices),
                            n=outliers.n, **tiles)
    return ref.outlier_spmm_ref(x, outliers.values, outliers.indices, outliers.m)


def sparse_linear_apply(x: jax.Array, packed: PackedNM,
                        outliers: StructuredOutliers | None,
                        backend: str | None = None, **tiles) -> jax.Array:
    """The production path: y = x @ (W_nm + O)^T, fused when possible."""
    backend = backend or _DEFAULT_BACKEND
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    if outliers is None:
        y = nm_matmul(x2, packed, backend=backend, **tiles)
    elif backend == "pallas":
        y = fused_sparse_linear(
            x2, packed.values, packed.packed_metadata(),
            outliers.values, pack_outlier_meta(outliers.indices),
            n=packed.n, m=packed.m, o_n=outliers.n, **tiles)
    else:
        y = ref.fused_sparse_linear_ref(
            x2, packed.values, packed.indices, packed.m,
            outliers.values, outliers.indices, outliers.m)
    return y.reshape(*orig_shape[:-1], y.shape[-1])
