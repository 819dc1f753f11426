"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels run in interpret mode (correctness
path, not representative of TPU time), so each row reports BOTH:
  - us_per_call of the jitted XLA-CPU *reference* path (what we can measure),
  - modeled v5e time from the kernel's byte/flop budget (what the roofline
    predicts): t = max(bytes/819e9, flops/197e12).
The derived column carries the modeled dense-vs-sparse speedup — the paper's
bandwidth argument, quantified per shape.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ActStats, SparsifyConfig, sparsify_linear
from repro.kernels import ref
from .common import emit

V5E_BW = 819e9
V5E_FLOPS = 197e12


def _time(fn, *args, reps=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def _modeled_us(b, out, kdim, sparse: bool, batch_bytes=2):
    flops = 2 * b * out * kdim
    w_bytes = out * kdim * (1.4375 if sparse else 2.0)
    io_bytes = (b * kdim + b * out) * batch_bytes
    t = max(w_bytes + io_bytes, 0) / V5E_BW
    t = max(t, flops / V5E_FLOPS)
    return t * 1e6


def run():
    shapes = [(16, 2048, 2048), (16, 4096, 4096), (128, 4096, 4096),
              (16, 4096, 14336)]
    for b, out, kdim in shapes:
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (out, kdim), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (b, kdim))
        st = ActStats.init(kdim).update(x)
        sl = sparsify_linear(w, st, SparsifyConfig())

        dense_fn = jax.jit(lambda x, w: x @ w.T)
        us_dense = _time(dense_fn, x, w)
        sparse_fn = jax.jit(lambda x: ref.fused_sparse_linear_ref(
            x, sl.nm.values, sl.nm.indices, sl.nm.m, sl.outliers.values,
            sl.outliers.indices, sl.outliers.m))
        us_sparse = _time(sparse_fn, x)

        m_dense = _modeled_us(b, out, kdim, sparse=False)
        m_sparse = _modeled_us(b, out, kdim, sparse=True)
        emit(f"kernel/nm_fused/{b}x{out}x{kdim}", us_sparse,
             f"cpu_dense_us={us_dense:.1f};v5e_model_dense_us={m_dense:.2f};"
             f"v5e_model_sparse_us={m_sparse:.2f};"
             f"modeled_speedup={m_dense/m_sparse:.2f}")


if __name__ == "__main__":
    run()
