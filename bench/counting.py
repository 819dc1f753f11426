"""Bytes and operations the served work requires, from the shapes alone.

Kept with the benchmark so that no change to the program changes how its
work is counted.  A compressed projection [out, in] under n:m with o_n:256
salient weights stores (``SparseWeight``'s deployed buffers):

  N:M values   out * in * n/m          in the value dtype (bf16: 2 B)
  N:M metadata out * in/m int32 words  (4-bit indices packed per block)
  outliers     out * in/256 * o_n      bf16 values
  outlier meta out * in/256 * o_n/4    int32 words (8-bit indices)
  row scales   out float32              (int8 values only)

and needs 2 operations per stored nonzero (its n/m share of the elements
plus its outliers) per token.  Dense matrices need 2 per element.  The
embedding is a gather: one row per token, no operations.  Attention needs
4 * heads * head_dim operations per visible key per token (QK^T and PV).
"""
from __future__ import annotations

from bench.weights import proj_shapes


def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[dtype]


def _pattern(s: str) -> tuple[int, int]:
    n, m = s.split(":")
    return int(n), int(m)


def proj_bytes(out: int, inp: int, wfmt: dict, dtype: str) -> int:
    if wfmt["format"] == "dense":
        return out * inp * _itemsize(dtype)
    n, m = _pattern(wfmt["weight_pattern"])
    o_n, o_m = _pattern(wfmt["outlier_pattern"])
    b = out * inp * n // m * _itemsize(wfmt["values"]) + out * inp // m * 4
    b += out * (inp // o_m) * o_n * 2 + out * (inp // o_m) * (o_n // 4) * 4
    if wfmt["values"] == "int8":
        b += out * 4
    return b


def proj_nonzeros(out: int, inp: int, wfmt: dict) -> int:
    if wfmt["format"] == "dense":
        return out * inp
    n, m = _pattern(wfmt["weight_pattern"])
    o_n, o_m = _pattern(wfmt["outlier_pattern"])
    return out * inp * n // m + out * (inp // o_m) * o_n


def weight_bytes(conf: dict) -> dict:
    """Stored bytes of every weight: per projection kind over all layers,
    the embedding, the LM head and the norms."""
    m, wfmt = conf["model"], conf["weights"]
    L, d, V = m["n_layers"], m["d_model"], m["vocab"]
    it = _itemsize(m["dtype"])
    out = {name: L * proj_bytes(o, i, wfmt, m["dtype"])
           for name, (o, i) in proj_shapes(m).items()}
    out["embed"] = V * d * it
    out["lm_head"] = 0 if m["tie_embeddings"] else V * d * it
    out["norms"] = (2 * L + 1) * d * it
    return out


def param_count(conf: dict) -> int:
    """Dense parameters of the model (norms left out, as the program's
    ``ModelConfig.param_count`` leaves them)."""
    m = conf["model"]
    n = sum(o * i for o, i in proj_shapes(m).values()) * m["n_layers"]
    return n + m["vocab"] * m["d_model"] * (1 if m["tie_embeddings"] else 2)


def _visible(pos: int, window) -> int:
    """Keys a query at position ``pos`` attends to."""
    return pos + 1 if window is None else min(pos + 1, window)


def token_flops(conf: dict) -> int:
    """Operations of one token through every layer's projections."""
    m, wfmt = conf["model"], conf["weights"]
    return 2 * m["n_layers"] * sum(proj_nonzeros(o, i, wfmt)
                                   for o, i in proj_shapes(m).values())


def attention_flops(conf: dict, pos: int) -> int:
    """Operations of one query at ``pos`` over all layers."""
    m = conf["model"]
    return (4 * m["n_heads"] * m["head_dim"] * _visible(pos, m["window"])
            * m["n_layers"])


def head_flops(conf: dict) -> int:
    m = conf["model"]
    return 2 * m["vocab"] * m["d_model"]


def call_flops(conf: dict, contexts, heads: int) -> int:
    """Operations a chunk or decode call requires: every real token
    through the projections and attention at its position, and the LM
    head for each row that emits a token.  ``contexts`` = [(cursor,
    new tokens)] per real row."""
    total = heads * head_flops(conf)
    per_tok = token_flops(conf)
    for cur, n in contexts:
        total += n * per_tok
        total += sum(attention_flops(conf, p) for p in range(cur, cur + n))
    return total


def kv_bytes_per_token(conf: dict) -> int:
    m = conf["model"]
    return (2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"]
            * _itemsize(m["dtype"]))


def decode_bytes(conf: dict, contexts) -> int:
    """Least HBM bytes of one decode call: every stored weight but the
    embedding, one embedding row per row, and the KV each row reads and
    writes at its context."""
    wb = weight_bytes(conf)
    m = conf["model"]
    it = _itemsize(m["dtype"])
    b = sum(v for k, v in wb.items() if k != "embed")
    kv = kv_bytes_per_token(conf)
    for cur, _ in contexts:
        b += m["d_model"] * it + kv * (_visible(cur, m["window"]) + 1)
    return b


def paged_attention_decode(conf: dict, contexts) -> tuple[int, int]:
    """(bytes, operations) the paged-attention kernel requires over all
    layers of one decode call: each row's query and output, and its
    visible keys and values."""
    m = conf["model"]
    it = _itemsize(m["dtype"])
    L, H, KV, hd = (m["n_layers"], m["n_heads"], m["n_kv_heads"],
                    m["head_dim"])
    b = f = 0
    for cur, _ in contexts:
        vis = _visible(cur, m["window"])
        b += L * (2 * H * hd * it + 2 * vis * KV * hd * it)
        f += attention_flops(conf, cur)
    return b, f


def least_seconds(nbytes: float, flops: float, peaks: dict) -> float:
    return max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
