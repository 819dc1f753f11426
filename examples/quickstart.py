"""Quickstart: the paper's pipeline on one weight matrix in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import (ActStats, SparsifyConfig, sparsify_linear,
                        dense_effective_weight, Pattern)
from repro.core.packing import pack_outlier_meta
from repro.kernels.fused_sparse_linear import fused_sparse_linear

key = jax.random.PRNGKey(0)

# A linear layer W[out, in] and some calibration activations with outliers
# in the first 16 input channels (the setting the paper targets).
W = jax.random.normal(key, (1024, 2048), jnp.float32) * 0.02
x_calib = jax.random.normal(jax.random.PRNGKey(1), (512, 2048))
x_calib = x_calib.at[:, :16].mul(20.0)
stats = ActStats.init(2048).update(x_calib)

# --- the 4-stage pipeline (stages 1-3; EBFT is stage 4, see sparsify_e2e) ---
cfg = SparsifyConfig(
    weight_pattern="8:16",      # paper's headline pattern
    outlier_pattern="16:256",   # SSP-for-SW: structured salient weights
    scorer="ria",               # importance metric
    use_smoothquant=True,       # stage 1: equalized scoring view
    use_variance_correction=True)  # stage 3
sl = sparsify_linear(W, stats, cfg)

print(f"pattern           : {cfg.weight_pattern} "
      f"({Pattern(8,16).configurations} configurations/block, "
      f"{Pattern(8,16).paper_bits_per_element()} bits/elem metadata)")
print(f"N:M invariant     : every 16-block keeps exactly 8 -> "
      f"{bool((sl.nm_mask.reshape(-1,16).sum(-1) == 8).all())}")
print(f"salient fraction  : {float(sl.salient_mask.mean()):.4f} "
      f"(16/256 = {16/256:.4f})")

# --- deployment: y = x @ (W_nm + outliers)^T via the fused sparse kernel ---
x = jax.random.normal(jax.random.PRNGKey(2), (8, 2048))
y_kernel = fused_sparse_linear(x, sl.nm.values, sl.nm.packed_metadata(),
                               sl.outliers.values,
                               pack_outlier_meta(sl.outliers.indices),
                               n=8, m=16, o_n=16)
y_dense = x @ dense_effective_weight(W, sl, cfg).T
print(f"fused kernel error: {float(jnp.abs(y_kernel - y_dense).max()):.2e}")

# --- what did compression buy? ---
dense_bytes = W.size * 2                                    # bf16 deploy
comp_bytes = (sl.nm.values.size * 2 + sl.nm.packed_metadata().size * 4
              + sl.outliers.values.size * 2 + sl.outliers.indices.size)
print(f"deployed bytes    : {dense_bytes/2**20:.2f} MiB -> "
      f"{comp_bytes/2**20:.2f} MiB ({dense_bytes/comp_bytes:.2f}x)")

# --- quality: relative output error vs the dense layer ---
err = jnp.linalg.norm(y_dense - x @ W.T) / jnp.linalg.norm(x @ W.T)
print(f"rel. output error : {float(err):.4f} (50% of weights removed)")
