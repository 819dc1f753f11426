"""Whether what the timed path served is correct.

Once the window has closed, a sample drawn from the seed of the requests
the engine finished, the longest among them, is run through the plain
reference (``bench/reference.py``) over each prompt with its served
tokens.  The number compared is the widest gap by which a served token's
reference logit lies below the reference's best at that position:
greedy serving should put each token within rounding of the reference's
first choice.  Every request of the mixes is greedy.

The control reads the same gap for the token that the reference computed
in float8 puts first, at the same positions of the same sequences, and is
judged by the same ``result``.

Beside the gap, the bytes of the weights the step programs are handed
must equal what the configuration states (``bench/counting.py``): a
change of the served weights' format (int8 or float8 values, to stream
fewer bytes) is not the stated model, even where its tokens read like
bfloat16's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import counting, lengths, reference


def sample(records, seed: int, n: int) -> list:
    """``n`` finished requests: the one with the most served tokens, and
    the rest drawn from the seed."""
    done = [r for r in records if r.req is not None and r.finished
            is not None and len(r.req.tokens) == r.spec.out_len]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.req.tokens), r.spec.index))
    rest = done[1:]
    rng = lengths.rng_for(seed, 3)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)),
                      replace=False).tolist() if rest else []
    return [done[0]] + [rest[i] for i in sorted(pick)]


def tree_bytes(tree) -> int:
    """Bytes of every array in ``tree`` (arrays or ShapeDtypeStructs)."""
    return sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def sequences(served, seed: int, vocab: int):
    """(sequence, positions, served tokens) of each sampled request, given
    as (spec, served tokens): the prompt plus every served token but the
    last, and the positions whose logits chose the served tokens."""
    seqs, rows, out = [], [], []
    for spec, toks in served:
        prompt = lengths.prompt_tokens(seed, spec.index, spec.prompt_len,
                                       vocab)
        seqs.append(prompt + toks[:-1])
        rows.append(list(range(len(prompt) - 1, len(prompt) - 1 + len(toks))))
        out.append(toks)
    return seqs, rows, out


@jax.jit
def _gaps(ref, chosen):
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]


def _reading(gaps: list, served) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"widest_gap": float(g.max()) if g.size else None,
            "mean_gap": float(g.mean()) if g.size else None,
            "share_off": float(np.mean(g > 0)) if g.size else None,
            "tokens": int(sum(len(t) for t in served)),
            "requests": len(served)}


def widest_gaps(conf: dict, seed: int, served_pairs,
                control: bool = False) -> dict:
    """Over (spec, served tokens): the program's widest gap ("widest_gap")
    and mean gap ("mean_gap"), the share of positions where it served
    another token than the reference's first ("share_off"), the tokens
    and requests compared; with ``control`` the same reading for the
    tokens that the float8 reference puts first ("control")."""
    vocab = conf["model"]["vocab"]
    seqs, rows, served = sequences(served_pairs, seed, vocab)
    precisions = ("f32", "fp8") if control else ("f32",)
    out = reference.logits(conf, seed, seqs, rows, precisions)
    prog, ctrl = [], []
    for i, toks in enumerate(served):
        ref = out["f32"][i]
        prog.append(np.asarray(_gaps(ref, jnp.asarray(toks, jnp.int32))))
        if control:
            c = jnp.argmax(out["fp8"][i], axis=-1).astype(jnp.int32)
            ctrl.append(np.asarray(_gaps(ref, c)))
    res = _reading(prog, served)
    if control:
        res["control"] = _reading(ctrl, served)
    return res


def result(reading: dict, conf: dict) -> tuple[bool, dict]:
    """(correct, the numbers compared with their limits).  The limits are
    the configuration's ``check``: the fewest served tokens that make a
    comparison ("min_tokens"), and an upper limit for each reading it
    names ("widest_gap", "mean_gap", "share_off"); and where the reading
    has "weight_bytes", the stored bytes that the configuration states."""
    limits = conf["check"]
    checked = {"tokens_compared": {"value": reading["tokens"],
                                   "limit": limits["min_tokens"],
                                   "holds": "at least"}}
    ok = reading["tokens"] >= limits["min_tokens"]
    for name in ("widest_gap", "mean_gap", "share_off"):
        if name in limits:
            v = reading[name]
            ok &= v is not None and bool(np.isfinite(v)) and v <= limits[name]
            checked[name] = {"value": v, "limit": limits[name],
                             "holds": "at most"}
    if "weight_bytes" in reading:
        stated = sum(counting.weight_bytes(conf).values())
        ok &= reading["weight_bytes"] == stated
        checked["weight_bytes"] = {"value": reading["weight_bytes"],
                                   "limit": stated, "holds": "equal to"}
    return bool(ok), checked
