"""Compressed storage for N:M-sparse weights + storage accounting.

The deployable layout (consumed by the Pallas kernels):

  values   : [out, in * N/M]       kept weight values, row-major by block
  indices  : [out, in/M, N] int32  position of each value inside its block
  packed   : [out, in/M]    int32  the same indices packed 4 bits each
                                   (valid for M <= 16, N <= 8 -> one word)

and for N:256 structured outliers (``pack_outlier_meta``):

  meta     : [out, in/256, N/4] int32  indices packed 8 bits x4 per word

``bits_per_element`` accounting reproduces paper Table 1:
  2:4  -> 0.75   (ceil(log2 6)=3 bits / 4)
  4:8  -> 0.8125 (two blocks share a 13-bit code: ceil(2*log2 70)=13 / 16)
  8:16 -> 0.875  (ceil(log2 12870)=14 / 16)
  16:32-> 1.0    (word-aligned dense bitmap)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .patterns import Pattern, parse_pattern, block_topn_indices


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PackedNM:
    """N:M compressed weight matrix (one linear layer, W[out, in])."""

    values: jax.Array    # [out, in//m * n]
    indices: jax.Array   # [out, in//m, n] int32 in [0, m)
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    in_dim: int = dataclasses.field(metadata=dict(static=True))

    @property
    def out_dim(self) -> int:
        return self.values.shape[0]

    @property
    def n_blocks(self) -> int:
        return self.in_dim // self.m

    def to_dense(self) -> jax.Array:
        out = self.values.shape[0]
        vals = self.values.reshape(out, self.n_blocks, self.n)
        onehot = jax.nn.one_hot(self.indices, self.m, dtype=vals.dtype)
        return jnp.einsum("obn,obnm->obm", vals, onehot).reshape(out, self.in_dim)

    def packed_metadata(self) -> jax.Array:
        """4-bit-packed indices, one int32 word per block (m<=16, n<=8)."""
        if self.m > 16 or self.n > 8:
            raise ValueError(f"word packing supports m<=16,n<=8; got {self.n}:{self.m}")
        shifts = (4 * jnp.arange(self.n, dtype=jnp.int32))[None, None, :]
        return jnp.sum(self.indices << shifts, axis=-1).astype(jnp.int32)

    def storage_bytes(self, value_bytes: int = 2) -> int:
        """Deployed bytes: values + enumerative metadata (paper accounting)."""
        p = Pattern(self.n, self.m)
        meta_bits = p.bits_per_element(pack_blocks=2 if (self.n, self.m) == (4, 8) else 1)
        total_elems = self.values.shape[0] * self.in_dim
        return int(self.values.size * value_bytes + total_elems * meta_bits / 8)


def unpack_metadata(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of PackedNM.packed_metadata: int32 word -> [.., n] indices."""
    shifts = (4 * jnp.arange(n, dtype=jnp.int32))
    return (packed[..., None] >> shifts) & 0xF


def pack_outlier_meta(indices: jax.Array) -> jax.Array:
    """[out, nb, n] int32 (0..255) -> [out, nb, n//4] int32, 8 bits each,
    index t in bits 8*(t % 4) of word t // 4."""
    out, nb, n = indices.shape
    assert n % 4 == 0
    grouped = indices.reshape(out, nb, n // 4, 4)
    shifts = 8 * jnp.arange(4, dtype=jnp.int32)
    return jnp.sum(grouped << shifts[None, None, None, :],
                   axis=-1).astype(jnp.int32)


def unpack_outlier_meta(meta: jax.Array, n: int) -> jax.Array:
    """Inverse of ``pack_outlier_meta``: [..., n//4] int32 -> [..., n]."""
    shifts = 8 * jnp.arange(4, dtype=jnp.int32)
    idx = (meta[..., None] >> shifts) & 0xFF
    return idx.reshape(*meta.shape[:-1], n)


def pack_nm(w_pruned: jax.Array, mask: jax.Array, pattern) -> PackedNM:
    """Compress an already-pruned dense matrix given its N:M mask.

    Uses the mask (not the values) to locate kept positions so that exact
    zeros among kept weights survive round-tripping.
    """
    p = parse_pattern(pattern)
    out, in_dim = w_pruned.shape
    idx = block_topn_indices(mask.astype(jnp.float32), p.n, p.m)  # kept positions
    blocks = w_pruned.reshape(out, in_dim // p.m, p.m)
    values = jnp.take_along_axis(blocks, idx, axis=-1)
    return PackedNM(values=values.reshape(out, -1), indices=idx,
                    n=p.n, m=p.m, in_dim=in_dim)


def dense_bytes(out_dim: int, in_dim: int, value_bytes: int = 2) -> int:
    return out_dim * in_dim * value_bytes


def compression_report(out_dim: int, in_dim: int, weight_pattern,
                       outlier_pattern=None, value_bytes: int = 2) -> dict:
    """Static storage accounting for one linear layer (used by benchmarks)."""
    wp = parse_pattern(weight_pattern)
    total = out_dim * in_dim
    vals = total * wp.density * value_bytes
    meta = total * wp.bits_per_element(pack_blocks=2 if (wp.n, wp.m) == (4, 8) else 1) / 8
    rep = {"dense_bytes": dense_bytes(out_dim, in_dim, value_bytes),
           "nm_value_bytes": int(vals), "nm_meta_bytes": int(meta)}
    if outlier_pattern is not None:
        op = parse_pattern(outlier_pattern)
        o_vals = total * op.density * value_bytes
        o_meta = total * op.n / op.m  # 8-bit index per salient value (m=256)
        rep["outlier_value_bytes"] = int(o_vals)
        rep["outlier_meta_bytes"] = int(o_meta)
    rep["compressed_bytes"] = sum(v for k, v in rep.items() if k != "dense_bytes")
    rep["ratio"] = rep["compressed_bytes"] / rep["dense_bytes"]
    return rep
