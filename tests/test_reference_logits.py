"""Engine prefill, then cached decode, through the paged pool, against the
benchmark's plain float32 reference (``bench/reference.py``: it
regenerates the seeded weights, compresses them by the configuration's
stated rule and runs a full forward pass, importing nothing of the
program), at the smoke sizes of ``bench/configs/mistral-7b.s816.json``:
8:16 + 16:256 weights, GQA with 4 query heads per KV head, and a 48-token
window that every prompt here outgrows, prefilled in chunks under the
token budget.  Logits are compared, not tokens: with random weights the
largest logit changes on rounding.

The model runs in float32 here, so the program and the reference differ
only in the order of their sums (chunked flash-style attention against
one softmax, the sparse linear's rebuilt weight against the reference's
own compression).  ``ATOL`` is set from that: a hundred times the largest
difference seen, 1e-3 of logits of order one, and still far below the
gap a missing window or bfloat16 rounding opens (the controls below).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, registry, run
from repro.serving import SamplingParams

SEED = 20261018
# largest |program - reference| logit seen is under 1e-5 (float32 sums in
# another order); 1e-3 leaves that a hundredfold room
ATOL = 1e-3
PROMPTS = (100, 71, 57)                 # all longer than the 48 window
GEN = 10


def _conf(**model):
    conf = registry.config("mistral-7b.s816")
    conf = run.merged(conf, conf["smoke"])
    return run.merged(conf, {"model": {"dtype": "float32", **model}})


def _served(conf, backend):
    """Per request: its prompt, served tokens, and the logits that chose
    them: the chunk step's at the prompt's last position, then each decode
    step's."""
    engine, _ = harness.build(run.merged(
        conf, {"deployment": {"paged_attention": backend}}), SEED)
    a = engine.adapter
    logits: dict = {}
    chunk, decode = a.step_chunk, a.step_decode

    def step_chunk(rows, lanes, cur, n_new, tokens):
        out = chunk(rows, lanes, cur, n_new, tokens)
        for i, slot in enumerate(rows):
            req = engine.running[slot]
            if int(cur[i]) + int(n_new[i]) == len(req.prompt):
                logits[req.request_id] = [out[i, int(n_new[i]) - 1]]
        return out

    def step_decode(tokens, active):
        out = decode(tokens, active)
        for slot in active:
            logits[engine.running[slot].request_id].append(out[slot, 0])
        return out
    a.step_chunk, a.step_decode = step_chunk, step_decode
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, conf["model"]["vocab"], n).tolist()
               for n in PROMPTS]
    reqs = [engine.submit(p, SamplingParams(max_new_tokens=GEN))
            for p in prompts]
    engine.run()
    return [(p, r.tokens, np.stack([np.asarray(x, np.float32)
                                    for x in logits[r.request_id]]))
            for p, r in zip(prompts, reqs)]


def _reference(conf, served):
    seqs = [p + toks[:-1] for p, toks, _ in served]
    rows = [list(range(len(p) - 1, len(p) - 1 + len(toks)))
            for p, toks, _ in served]
    return [np.asarray(x) for x in
            reference.logits(conf, SEED, seqs, rows)["f32"]]


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_prefill_then_decode_logits_match_reference(backend):
    """Both paged-attention paths the engine serves from (the XLA gather
    and the Pallas kernel, interpreted here)."""
    conf = _conf()
    assert conf["model"]["n_heads"] == 4 * conf["model"]["n_kv_heads"]
    assert min(PROMPTS) > conf["model"]["window"]
    assert max(PROMPTS) > conf["deployment"]["token_budget"]
    served = _served(conf, backend)
    for (_, toks, got), want in zip(served, _reference(conf, served)):
        assert len(toks) == GEN and got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


@pytest.mark.parametrize("control", [{"window": None},
                                     {"dtype": "bfloat16"}],
                         ids=["no-window", "bfloat16"])
def test_controls_fall_outside_the_tolerance(control):
    """The controls: the same engine serving without its window, or in
    bfloat16 (a precision below the float32 both sides compute in here),
    reads logits far outside ``ATOL`` of the reference."""
    conf = _conf()
    served = _served(_conf(**control), "reference")
    diff = max(float(np.max(np.abs(got - want))) for (_, _, got), want
               in zip(served, _reference(conf, served)))
    assert diff > 10 * ATOL, diff
