"""Weights made on the device from ``--seed``.

The dense values come from one place, ``layer_dense`` and ``outer_dense``,
so the served program and the plain reference start from the same numbers
without the reference reading anything the program made: each regenerates
them from the seed.  ``served_params`` builds the program's parameter tree
in one jitted call, layer by layer under ``lax.map``, so a compressed model
never holds its dense copy whole (a 7B model's 14.5 GB of dense bf16
weights would not fit one 16 GB chip beside its compressed copy).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PROJ = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("attn_norm", "mlp_norm")
# norm scales are drawn small and non-zero so that the check covers them
# (the model multiplies by 1 + scale)
NORM_STD = 0.1


def base_key(seed: int):
    """A PRNG key for any whole seed, 64-bit ones included."""
    seed = int(seed) % 2**62
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31),
                              seed // 2**31)


def proj_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """[out, in] of each projection of one layer."""
    d, hd, ff = m["d_model"], m["head_dim"], m["d_ff"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return {"wq": (q, d), "wk": (kv, d), "wv": (kv, d), "wo": (d, q),
            "w_gate": (ff, d), "w_up": (ff, d), "w_down": (d, ff)}


def _dtype(m: dict):
    return jnp.dtype(m["dtype"])


def layer_dense(key, m: dict, i) -> dict:
    """Layer ``i``'s dense weights in the model's dtype: projections
    N(0, 1/in), norm scales N(0, NORM_STD^2)."""
    k = jax.random.fold_in(key, i + 1)
    dt = _dtype(m)
    out = {}
    for j, (name, (o, n)) in enumerate(proj_shapes(m).items()):
        w = jax.random.normal(jax.random.fold_in(k, j), (o, n), jnp.float32)
        out[name] = (w / math.sqrt(n)).astype(dt)
    for j, name in enumerate(NORMS):
        s = jax.random.normal(jax.random.fold_in(k, 100 + j),
                              (m["d_model"],), jnp.float32)
        out[name] = (NORM_STD * s).astype(dt)
    return out


def outer_dense(key, m: dict) -> dict:
    """Embedding, LM head and final norm."""
    k = jax.random.fold_in(key, 0)
    V, d = m["vocab"], m["d_model"]
    dt = _dtype(m)
    embed = 0.02 * jax.random.normal(jax.random.fold_in(k, 0), (V, d),
                                     jnp.float32)
    head = jax.random.normal(jax.random.fold_in(k, 1), (V, d),
                             jnp.float32) / math.sqrt(d)
    norm = NORM_STD * jax.random.normal(jax.random.fold_in(k, 2), (d,),
                                        jnp.float32)
    return {"embed": embed.astype(dt), "lm_head": head.astype(dt),
            "final_norm": norm.astype(dt)}


def params_fn(m: dict, compress=None):
    """The jitted ``key -> parameter tree`` of the program.
    ``compress(w) -> served weight`` turns one dense [out, in] projection
    into its served form (None serves it dense)."""
    def one(i, key):
        w = layer_dense(key, m, i)
        if compress is not None:
            for name in PROJ:
                w[name] = compress(w[name])
        return w

    @jax.jit
    def make(key):
        layers = jax.lax.map(lambda i: one(i, key), jnp.arange(m["n_layers"]))
        outer = outer_dense(key, m)
        return {"embed": outer["embed"], "layers": layers,
                "final_norm": outer["final_norm"],
                "lm_head": outer["lm_head"]}

    return make


def served_params(seed: int, m: dict, compress=None):
    """The program's parameter tree, made on the device in one jitted
    call from the seed."""
    return params_fn(m, compress)(base_key(seed))
