"""Sparse deployment: swap dense projection weights for compressed N:M (+
structured outlier) containers — the paper's serving story.

``SparseWeight`` is a pytree whose array leaves are exactly the deployed
buffers (bf16 values + bit-packed int32 metadata), so a lowered serving graph
reads compressed bytes from HBM:

  8:16 + 16:256 outliers, bf16:   1.30 B/elem  vs dense 2 B/elem (1.54x)

``layers.linear`` dispatches on this type, so every model in the zoo serves
sparse without code changes.  The served path is ``sparse_apply`` on every
backend, the TPU included: it unpacks the metadata with bit ops and rebuilds
the dense weight by a one-hot einsum inside the step.  The fused Pallas
kernel (``sparse_apply_pallas``) computes the same product from the packed
buffers and is tested against it, but nothing serves through it yet.
"""
from __future__ import annotations

import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp

from ..core.packing import PackedNM, pack_nm, unpack_metadata
from ..core.outliers import StructuredOutliers
from ..core.pipeline import SparsifyConfig, sparsify_linear
from ..core.patterns import parse_pattern
from ..core import scoring


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SparseWeight:
    """Compressed linear weight; stands in for a dense [out, in] array.

    May carry a leading stacked-layer dim on every array leaf.

    Beyond-paper: ``v_scale`` is not None => nm_values are int8 with a
    per-output-row absmax scale (sparsity x quantization composition;
    outlier values stay exact bf16 — they are the weights quantization
    hurts most, so SSP-for-SW doubles as the outlier store for int8)."""

    nm_values: jax.Array                  # [..., out, in*n/m] bf16 | int8
    nm_meta: jax.Array                    # [..., out, in/m] int32, 4-bit idx
    o_values: jax.Array | None            # [..., out, in/256, o_n]
    o_meta: jax.Array | None              # [..., out, in/256, o_n/4] int32
    v_scale: jax.Array | None             # [..., out] f32 (int8 mode)
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    o_n: int = dataclasses.field(metadata=dict(static=True))
    in_dim: int = dataclasses.field(metadata=dict(static=True))

    @property
    def ndim(self):          # so models can treat it like an array
        return self.nm_values.ndim

    @property
    def shape(self):
        return (*self.nm_values.shape[:-1], self.in_dim)

    def deployed_bytes(self) -> int:
        """Bytes this container actually ships to HBM — every deployed
        buffer counts, including the per-row f32 scales of int8 mode
        (omitting v_scale overstated the int8 compression ratio)."""
        return sum(v.size * v.dtype.itemsize
                   for v in (self.nm_values, self.nm_meta, self.o_values,
                             self.o_meta, self.v_scale) if v is not None)


def _unpack_8bit(meta: jax.Array, n: int) -> jax.Array:
    shifts = 8 * jnp.arange(4, dtype=jnp.int32)
    idx = (meta[..., None] >> shifts) & 0xFF
    return idx.reshape(*meta.shape[:-1], n)


def sparse_apply(sw: SparseWeight, x: jax.Array) -> jax.Array:
    """y = x @ W_hat^T from compressed buffers (portable path)."""
    out = sw.nm_values.shape[-2]
    nb = sw.in_dim // sw.m
    idx = unpack_metadata(sw.nm_meta, sw.n)                     # [out, nb, n]
    nm_vals = sw.nm_values
    if sw.v_scale is not None:                                  # int8 mode
        nm_vals = (nm_vals.astype(jnp.float32)
                   * sw.v_scale[..., None].astype(jnp.float32)).astype(x.dtype)
    vals = nm_vals.reshape(out, nb, sw.n)
    onehot = jax.nn.one_hot(idx, sw.m, dtype=vals.dtype)
    w = jnp.einsum("obn,obnm->obm", vals, onehot).reshape(out, sw.in_dim)
    if sw.o_values is not None:
        ob = sw.in_dim // 256
        o_idx = _unpack_8bit(sw.o_meta, sw.o_n)
        o_onehot = jax.nn.one_hot(o_idx, 256, dtype=sw.o_values.dtype)
        w = w + jnp.einsum("obn,obnm->obm", sw.o_values, o_onehot
                           ).reshape(out, sw.in_dim)
    return jnp.einsum("...k,ok->...o", x, w.astype(x.dtype))


def sparse_apply_pallas(sw: SparseWeight, x: jax.Array) -> jax.Array:
    """TPU path: fused Pallas kernel on the packed buffers.  int8 values
    stream quantized all the way into VMEM; the per-row scale rides as a
    kernel operand and dequantizes in-register after the gather."""
    from ..kernels.fused_sparse_linear import fused_sparse_linear
    from ..kernels.nm_spmm import nm_spmm
    scale = None if sw.v_scale is None else sw.v_scale.astype(jnp.float32)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, sw.in_dim)
    if sw.o_values is None:
        y = nm_spmm(x2, sw.nm_values, sw.nm_meta, n=sw.n, m=sw.m,
                    scale=scale)
    else:
        y = fused_sparse_linear(x2, sw.nm_values, sw.nm_meta, sw.o_values,
                                sw.o_meta, n=sw.n, m=sw.m, o_n=sw.o_n,
                                scale=scale)
    return y.reshape(*lead, -1)


def densify(sw: SparseWeight) -> jax.Array:
    """Dense [..., out, in] reconstruction of the deployed buffers.

    Inverse of the compression for serving purposes: running the engine on
    ``densify_params(sparse)`` computes the same function as serving the
    compressed containers, through the dense matmul path instead of the
    sparse kernels.  The reconstruction einsums run in the deployed value
    dtype — the same precision ``sparse_apply`` accumulates the one-hot
    decompression in — so the two realizations agree to fusion rounding.
    The speculative bench leans on this: the 8:16 draft's "dense
    counterpart" target is its own densification, giving a deterministic
    high-acceptance pair without trained weights.
    """
    lead = sw.nm_values.shape[:-1]                       # [..., out]
    nb = sw.in_dim // sw.m
    nm_vals = sw.nm_values
    if sw.v_scale is not None:                           # int8 mode
        nm_vals = (nm_vals.astype(jnp.float32)
                   * sw.v_scale[..., None].astype(jnp.float32)
                   ).astype(jnp.bfloat16)
    idx = unpack_metadata(sw.nm_meta, sw.n)              # [..., nb, n]
    vals = nm_vals.reshape(*lead, nb, sw.n)
    onehot = jax.nn.one_hot(idx, sw.m, dtype=vals.dtype)
    w = jnp.einsum("...bn,...bnm->...bm", vals, onehot
                   ).reshape(*lead, sw.in_dim)
    if sw.o_values is not None:
        o_idx = _unpack_8bit(sw.o_meta, sw.o_n)
        o_onehot = jax.nn.one_hot(o_idx, 256, dtype=sw.o_values.dtype)
        w = w + jnp.einsum("...bn,...bnm->...bm", sw.o_values, o_onehot
                           ).reshape(*lead, sw.in_dim)
    return w


def densify_params(params):
    """Replace every SparseWeight in a served pytree with its dense
    reconstruction (see ``densify``); dense leaves pass through."""
    return jax.tree_util.tree_map(
        lambda leaf: densify(leaf) if isinstance(leaf, SparseWeight) else leaf,
        params, is_leaf=lambda leaf: isinstance(leaf, SparseWeight))


# --------------------------------------------------------------------------
# conversion
# --------------------------------------------------------------------------

PRUNABLE = re.compile(
    r"wq|wk|wv|wo|w_gate|w_up|w_down|ws_gate|ws_up|ws_down|in_proj|out_proj|"
    r"w_q|w_k|w_v|w_slstm|c_wq|c_wk|c_wv|c_wo")
SKIP = re.compile(r"norm|embed|lm_head|router|gates|A_log|dt_bias|\bD\b")


def _to_sparse_weight(w2d: jax.Array, scfg: SparsifyConfig,
                      stats=None, quantize: bool = False) -> SparseWeight:
    sl = sparsify_linear(w2d, stats, scfg)
    nm = sl.nm
    o = sl.outliers
    from ..kernels.outlier_spmm import pack_outlier_meta
    values, v_scale = nm.values, None
    if quantize:
        absmax = jnp.max(jnp.abs(values.astype(jnp.float32)), axis=-1)
        v_scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        values = jnp.clip(jnp.round(values.astype(jnp.float32)
                                    / v_scale[..., None]), -127, 127
                          ).astype(jnp.int8)
    return SparseWeight(
        nm_values=values, nm_meta=nm.packed_metadata(),
        o_values=None if o is None else o.values,
        o_meta=None if o is None else pack_outlier_meta(o.indices),
        v_scale=v_scale,
        n=nm.n, m=nm.m, o_n=0 if o is None else o.n, in_dim=nm.in_dim)


def _leaf_cfg(name: str, leaf, scfg: SparsifyConfig) -> SparsifyConfig | None:
    """Per-leaf config (or None = keep dense). Mirrors core.pipeline's
    degradation: layers too narrow for a 256-block lose outlier recovery
    but are still N:M-pruned."""
    if not hasattr(leaf, "ndim") or leaf.ndim not in (2, 3):
        return None
    if SKIP.search(name) or not PRUNABLE.search(name.split("/")[-1]):
        return None
    wp = parse_pattern(scfg.weight_pattern)
    if leaf.shape[-1] % wp.m:
        return None
    if scfg.outlier_pattern is not None and leaf.shape[-1] % 256:
        return dataclasses.replace(scfg, outlier_pattern=None)
    return scfg


def sparsify_for_serving(params, scfg: SparsifyConfig, stats_by_name=None,
                         quantize: bool = False):
    """Replace eligible projections with SparseWeight; returns (params, report).

    ``quantize=True``: int8 N:M values + exact bf16 structured outliers
    (beyond-paper sparsity x quantization composition)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    new_leaves, dense_bytes, comp_bytes, n_sp = [], 0, 0, 0
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        leaf_cfg = _leaf_cfg(name, leaf, scfg)
        if leaf_cfg is None:
            new_leaves.append(leaf)
            continue
        st = (stats_by_name or {}).get(name)
        conv = partial(_to_sparse_weight, scfg=leaf_cfg, quantize=quantize)
        if leaf.ndim == 3:
            # one layer at a time, so the scoring and packing temporaries
            # held beside the dense weights are one layer's, not those of
            # the whole [L, out, in] stack
            sw = jax.lax.map(lambda w: conv(w, stats=None), leaf) \
                if st is None else jax.lax.map(
                    lambda a: conv(a[0], stats=a[1]), (leaf, st))
        else:
            sw = conv(leaf, stats=st)
        n_sp += 1
        dense_bytes += leaf.size * leaf.dtype.itemsize
        comp_bytes += sw.deployed_bytes()
        new_leaves.append(sw)
    new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
    report = {"n_layers_sparsified": n_sp, "dense_bytes": dense_bytes,
              "compressed_bytes": comp_bytes,
              "ratio": comp_bytes / max(dense_bytes, 1)}
    return new_params, report
