"""The plain reference: the served model's mathematics in float32.

It imports nothing of the program.  From the seed it regenerates the same
dense weights the program was given (``bench/weights.py``), compresses them
by the configuration's stated rule, and runs a straightforward forward
pass, one layer at a time over every sampled sequence, at ``HIGHEST``
matmul precision:

  compression (magnitude scores, per weight matrix [out, in]):
    salient   the top ``o_n`` |w| of every 256-wide block of a row, kept
              exactly (16:256)
    N:M       the top ``n`` |w| of every ``m``-wide block (8:16)
    variance  kept non-salient weights times sqrt(Var(W) / Var(kept)),
              biased variances over the whole matrix, then stored in the
              model's dtype
    W_eff     that, plus the salient weights at their positions
  block       RMSNorm with (1 + scale), GQA attention with RoPE (halves
              rotated), causal and window-limited, SwiGLU MLP, residuals
  head        final RMSNorm, then the LM head

The control (``precision="fp8"``) is the same computation in float8
e4m3, the nearest precision below the configuration's bfloat16: every
tensor that the program holds in bfloat16 (weights, the residual stream,
the embedding, q, k, v, every matmul's inputs and outputs, the MLP's
gated product) is rounded to float8 under a per-tensor scale, while
products accumulate, and norms, RoPE and softmax compute, in float32 as
they do in the program.  The logits stay float32, so that no tie among
float8 logits decides which token the control puts first.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
# queries per block of the attention, and the multiple sequences are
# padded to (so few shapes compile)
Q_BLOCK = 512


def _put(shape, idx):
    return jnp.put_along_axis(jnp.zeros(shape, bool), idx, True, axis=-1,
                              inplace=False)


def compress(w, wfmt: dict):
    """The effective [out, in] float32 weight of a served projection."""
    wf = w.astype(jnp.float32)
    if wfmt["format"] == "dense":
        return wf
    n, m = (int(v) for v in wfmt["weight_pattern"].split(":"))
    o_n, o_m = (int(v) for v in wfmt["outlier_pattern"].split(":"))
    out, k = wf.shape
    a = jnp.abs(wf)
    _, oi = jax.lax.top_k(a.reshape(out, k // o_m, o_m), o_n)
    salient = _put((out, k // o_m, o_m), oi).reshape(out, k)
    _, ki = jax.lax.top_k(a.reshape(out, k // m, m), n)
    kept = _put((out, k // m, m), ki).reshape(out, k) & ~salient
    var_all = jnp.var(wf)
    cnt = jnp.sum(kept)
    mean = jnp.sum(jnp.where(kept, wf, 0.0)) / cnt
    var_kept = jnp.sum(jnp.where(kept, (wf - mean) ** 2, 0.0)) / cnt
    factor = jnp.sqrt(var_all / (var_kept + 1e-12))
    corrected = jnp.where(kept, wf * factor, 0.0).astype(w.dtype)
    return corrected.astype(jnp.float32) + jnp.where(salient, wf, 0.0)


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _rope(x, theta: float):
    """x [S, heads, hd] at positions 0..S-1, halves rotated."""
    S, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _q8(x, fp8: bool):
    """Where the program holds a tensor in bfloat16, the control holds it
    in float8 (per-tensor scale)."""
    return _fp8(x) if fp8 else x


def _block_one(x, w, mm: tuple, fp8: bool):
    """One decoder layer over one sequence x [S, d] (S a multiple of
    Q_BLOCK; positions past the sequence are padding, which causality
    keeps out of every real position)."""
    H, KV, hd, theta, eps, window = mm
    S = x.shape[0]
    q8 = partial(_q8, fp8=fp8)
    x = q8(x)
    h = q8(_norm(x, w["attn_norm"], eps))
    q = q8(_mm("sd,od->so", h, w["wq"], fp8)).reshape(S, H, hd)
    k = q8(_mm("sd,od->so", h, w["wk"], fp8)).reshape(S, KV, hd)
    v = q8(_mm("sd,od->so", h, w["wv"], fp8)).reshape(S, KV, hd)
    q, k = q8(_rope(q, theta)), q8(_rope(k, theta))
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    kpos = jnp.arange(S)

    def attend(qb_i):
        qb, i0 = qb_i
        s = _mm("qhd,khd->hqk", qb, k, fp8) / math.sqrt(hd)
        qpos = i0 + jnp.arange(Q_BLOCK)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", p, v, fp8)

    nq = S // Q_BLOCK
    o = q8(jax.lax.map(attend, (q.reshape(nq, Q_BLOCK, H, hd),
                                jnp.arange(nq) * Q_BLOCK)))
    x = q8(x + q8(_mm("sf,df->sd", o.reshape(S, H * hd), w["wo"], fp8)))
    h = q8(_norm(x, w["mlp_norm"], eps))
    g = q8(_mm("sd,fd->sf", h, w["w_gate"], fp8))
    u = q8(_mm("sd,fd->sf", h, w["w_up"], fp8))
    a = q8(jax.nn.silu(g) * u)
    return x + q8(_mm("sf,df->sd", a, w["w_down"], fp8))


@partial(jax.jit, static_argnames=("mm", "fp8"))
def _block(xs, w, mm: tuple, fp8: bool):
    """One decoder layer over every sequence, xs [n, S, d]."""
    return jax.lax.map(lambda x: _block_one(x, w, mm, fp8), xs)


@partial(jax.jit, static_argnames=("wfmt", "m"))
def _layer(key, i, wfmt, m):
    w = weights.layer_dense(key, dict(m), i)
    wf = dict(wfmt)
    return {name: (compress(v, wf) if name in weights.PROJ
                   else v.astype(jnp.float32)) for name, v in w.items()}


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, norm, head, eps: float, fp8: bool):
    x = _q8(x, fp8)
    return _mm("sd,vd->sv", _q8(_norm(x, norm, eps), fp8),
               head.astype(jnp.float32), fp8)


def _frozen(d: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items()
                        if not isinstance(v, (dict, list))))


def logits(conf: dict, seed: int, seqs: list[list[int]], rows: list,
           precisions=("f32",)) -> dict:
    """Reference logits, on the device, of each sequence at the positions
    in ``rows[i]`` ([S_i] tokens; positions index them), for each of
    ``precisions`` ("f32", "fp8"): {precision: [array [len(rows[i]), V]]}.
    Every sequence is padded to the longest, rounded up to Q_BLOCK, so
    that one program per length serves all of them."""
    m = conf["model"]
    mm = (m["n_heads"], m["n_kv_heads"], m["head_dim"], float(m["rope_theta"]),
          float(m["norm_eps"]), m["window"])
    key = weights.base_key(seed)
    outer = jax.jit(lambda k: weights.outer_dense(k, m))(key)
    S = -(-max(len(s) for s in seqs) // Q_BLOCK) * Q_BLOCK
    tok = jnp.asarray(np.stack([np.pad(np.asarray(s, np.int32),
                                       (0, S - len(s))) for s in seqs]))
    x0 = outer["embed"][tok].astype(jnp.float32)
    xs = {p: x0 for p in precisions}
    fm, fw = _frozen(m), _frozen(conf["weights"])
    for i in range(m["n_layers"]):
        w = _layer(key, i, fw, fm)
        for p in precisions:
            xs[p] = _block(xs[p], w, mm, p == "fp8")
        del w
    out = {}
    for p in precisions:
        out[p] = [_head(xs[p][j, jnp.asarray(r)], outer["final_norm"],
                        outer["lm_head"], float(m["norm_eps"]), p == "fp8")
                  for j, r in enumerate(rows)]
    return out
