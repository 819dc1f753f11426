"""Speculative decoding: draft-verify over the serving engine's pools.

The load-bearing property (ISSUE 8 acceptance): GREEDY speculative decode
is token-identical to non-speculative greedy decode — for dense and
8:16+outlier targets, in both KV layouts, with every proposer (self-draft,
a genuinely different draft model forcing rejection rollbacks, n-gram
prompt lookup), through preemption mid-speculation, and on a 1x8 mesh.
Speculation may only change WHEN tokens arrive, never WHICH tokens.

Also pinned here: the leave-one-in ``verify_draft`` unit semantics, the
token-budget verify reserve, acceptance-driven per-request k adaptation,
the speculative counters/phases of the PR-7 observability substrate, and
the jit-variant growth cap (S = k+1 shapes ride the ``_bucket`` ladder —
compiled variants stay logarithmic in k, not linear).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro import configs
from repro.core import SparsifyConfig
from repro.models import get_model
from repro.serving import (Request, SamplingParams, ServingEngine,
                           ServingTracer, SpeculativeConfig, Status,
                           spec_verify_reserve)
from repro.serving.sampling import verify_draft
from repro.serving.speculative import NGramProposer

CFG = dataclasses.replace(configs.get_smoke("llama-paper"),
                          name="spec-test", n_layers=2, d_model=128,
                          n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
                          vocab=512, remat=False)
GEN = 10


@pytest.fixture(scope="module")
def dense_params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def other_params():
    """A second, unrelated init: a draft model that genuinely disagrees
    with the target, so verification exercises rejection + rollback."""
    return get_model(CFG).init(jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def sparse_params(dense_params):
    from repro.models.sparse_serving import sparsify_for_serving
    scfg = SparsifyConfig(weight_pattern="8:16", outlier_pattern="16:256",
                          scorer="magnitude", use_smoothquant=False)
    sp, report = sparsify_for_serving(dense_params, scfg)
    assert report["n_layers_sparsified"] > 0
    return sp


def _prompts(n, length, seed=1):
    key = jax.random.PRNGKey(seed)
    return [t.tolist() for t in
            jax.random.randint(key, (n, length), 0, CFG.vocab)]


def _run(params, prompts, gen=GEN, *, draft=None, samplings=None, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 48)
    engine = ServingEngine(CFG, params, draft=draft, **kw)
    samplings = samplings or [SamplingParams(max_new_tokens=gen)] * len(prompts)
    reqs = [engine.submit(p, s) for p, s in zip(prompts, samplings)]
    engine.run()
    return engine, reqs


# ---------------------------------------------------------------------------
# the tentpole property: greedy speculation is exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["slot", "paged"])
@pytest.mark.parametrize("proposer", ["self", "other", "ngram"])
@pytest.mark.parametrize("target", ["dense", "sparse"])
def test_greedy_token_identical(target, proposer, layout, dense_params,
                                other_params, sparse_params):
    params = dense_params if target == "dense" else sparse_params
    prompts = _prompts(3, 16)
    _, base = _run(params, prompts)
    if proposer == "ngram":
        draft = SpeculativeConfig(k=3, method="ngram")
    else:
        dp = params if proposer == "self" else other_params
        draft = SpeculativeConfig(k=3, method="model", params=dp, cfg=CFG)
    engine, reqs = _run(params, prompts, draft=draft, kv_layout=layout)
    for b, r in zip(base, reqs):
        assert r.status is Status.FINISHED
        assert r.tokens == b.tokens, \
            f"{target}/{proposer}/{layout} diverged"
        assert len(r.logprobs) == len(r.tokens)
        assert all(lp <= 1e-6 for lp in r.logprobs)
    st = engine.stats()["speculative"]
    if proposer == "self":
        # a self-draft agrees with the target everywhere: everything
        # proposed is accepted, and the request finishes in far fewer
        # engine steps than one-token-per-step decode
        assert st["acceptance_rate"] == 1.0
        assert st["accepted_per_step"] > 1.0
    if proposer == "other":
        # an unrelated init must disagree sometimes — otherwise this
        # matrix never exercises rejection rollback
        assert st["drafted"] > 0
        assert st["accepted"] < st["drafted"]


def test_sparse_drafts_its_densified_counterpart(sparse_params):
    """The ISSUE headline pair: the 8:16+outlier compressed model drafts
    for its dense counterpart (here the exact densification, standing in
    for a trained above-threshold pair) — near-total acceptance while the
    draft runs the sparse kernels and the target the dense matmuls."""
    from repro.models.sparse_serving import densify_params
    target = densify_params(sparse_params)
    prompts = _prompts(3, 16)
    _, base = _run(target, prompts)
    draft = SpeculativeConfig(k=3, method="model", params=sparse_params,
                              cfg=CFG)
    engine, reqs = _run(target, prompts, draft=draft, kv_layout="paged")
    for b, r in zip(base, reqs):
        assert r.tokens == b.tokens
    st = engine.stats()["speculative"]
    assert st["acceptance_rate"] > 0.9
    assert st["accepted_per_step"] > 1.0


def test_speculation_takes_fewer_steps(dense_params):
    prompts = _prompts(3, 16)
    base_engine, _ = _run(dense_params, prompts)
    spec_engine, _ = _run(
        dense_params, prompts,
        draft=SpeculativeConfig(k=3, method="model", params=dense_params,
                                cfg=CFG))
    assert spec_engine.n_steps < base_engine.n_steps


def test_preemption_mid_speculation(dense_params, other_params):
    """A paged arena too small for every request's speculative burst: the
    k+1-token prepare_decode preempts the youngest mid-speculation, and
    the preempted request resumes (draft cursor reset via on_admit) with
    its stream intact."""
    prompts = _prompts(4, 16)
    _, base = _run(dense_params, prompts)          # roomy baseline
    draft = SpeculativeConfig(k=4, method="model", params=other_params,
                              cfg=CFG)
    # 4 requests need 28 blocks at full length; 24 forces preempt-to-queue
    # while speculative bursts are in flight
    engine, reqs = _run(dense_params, prompts, draft=draft,
                        kv_layout="paged", max_len=48, block_size=4,
                        n_blocks=24, prefix_caching=False)
    assert engine.n_preemptions > 0, \
        "arena sized to force preemption mid-speculation"
    for b, r in zip(base, reqs):
        assert r.status is Status.FINISHED
        assert r.tokens == b.tokens


def test_spec_budget_charges_verify_tokens(dense_params, other_params):
    """With speculation on, each step reserves k+1 verify tokens per
    decoding request out of the prefill budget — a late-arriving prompt
    chunks through the remainder and every stream still matches the
    non-speculative engine's."""
    prompts = _prompts(2, 16) + _prompts(1, 24, seed=9)
    kw = dict(n_slots=4, max_len=48, token_budget=16)
    _, base = _run(dense_params, prompts, **kw)
    draft = SpeculativeConfig(k=3, method="model", params=other_params,
                              cfg=CFG)
    _, reqs = _run(dense_params, prompts, draft=draft, **kw)
    for b, r in zip(base, reqs):
        assert r.status is Status.FINISHED
        assert r.tokens == b.tokens


needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

MESH_CFG = dataclasses.replace(CFG, name="spec-mesh-test", n_heads=8,
                               n_kv_heads=8, head_dim=16)


@needs8
def test_mesh_parity():
    """1x8 model-axis mesh: speculative token streams identical to the
    single-device speculative engine AND to the unmeshed non-speculative
    engine — draft params co-resident under the same placement."""
    params = get_model(MESH_CFG).init(jax.random.PRNGKey(0))
    other = get_model(MESH_CFG).init(jax.random.PRNGKey(7))
    mesh = jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    prompts = _prompts(3, 16)
    draft = SpeculativeConfig(k=3, method="model", params=other,
                              cfg=MESH_CFG)

    def run(mesh_, draft_):
        engine = ServingEngine(MESH_CFG, params, n_slots=4, max_len=48,
                               mesh=mesh_, draft=draft_)
        reqs = [engine.submit(p, SamplingParams(max_new_tokens=GEN))
                for p in prompts]
        engine.run()
        assert all(r.status is Status.FINISHED for r in reqs)
        return [r.tokens for r in reqs]

    base = run(None, None)
    assert run(None, draft) == base
    assert run(mesh, draft) == base


# ---------------------------------------------------------------------------
# satellite: jit-variant growth stays bucketed
# ---------------------------------------------------------------------------

def test_jit_variant_growth_is_bucketed(dense_params):
    """Adaptive k walks 1..max_k over a long generation; the verify step's
    S = k+1 axis must ride the power-of-two ``_bucket`` ladder, so the
    number of compiled step variants (the executables each jitted
    program holds) stays logarithmic in k — NOT one variant per k.  A
    self-draft accepts everything, so k actually climbs 1 -> max_k (a
    disagreeing draft would pin k at min_k and never exercise the
    ladder)."""
    tracer = ServingTracer()
    draft = SpeculativeConfig(k=1, min_k=1, max_k=8, method="model",
                              params=dense_params, cfg=CFG)
    prompts = _prompts(3, 16)
    engine, reqs = _run(dense_params, prompts, gen=24, max_len=64,
                        draft=draft, tracer=tracer)
    assert all(r.status is Status.FINISHED for r in reqs)
    ks = {r.draft_k for r in reqs}
    assert ks - {1}, "adaptive k never moved; the ladder was not exercised"
    target = engine.adapter
    drafter = engine.spec.drafter.adapter
    variants = {"step": target._step_fn._cache_size(),
                "draft_step": drafter._step_fn._cache_size(),
                "draft_decode": drafter._decode_fn._cache_size()}
    # target verify/prefill chunks ("step"): S in {bucketed prompt} union
    # {2, 4, 8, 16} for k+1 — a per-k retrace would give ~max_k variants
    assert 1 <= variants["step"] <= 6, variants
    # drafter catch-up + decode variants are bucketed the same way
    assert variants["draft_step"] <= 6, variants
    assert variants["draft_decode"] <= 2, variants
    # each of them was one compile JAX reported to the tracer, the
    # drafter's under serve.draft and the target's under its own phases
    compiles = tracer.registry.counter("serving_jit_compiles_total")

    def n_compiles(*phases):
        return sum(compiles.get(fn="jit(serve_step)", phase=p,
                                engine=tracer.name) for p in phases)
    assert n_compiles("serve.draft") == \
        variants["draft_step"] + variants["draft_decode"]
    assert n_compiles("serve.chunk", "serve.decode") == \
        variants["step"] + target._decode_fn._cache_size()


def test_spec_counters_and_phases(dense_params):
    tracer = ServingTracer()
    draft = SpeculativeConfig(k=3, method="model", params=dense_params,
                              cfg=CFG)
    _run(dense_params, _prompts(2, 16), draft=draft, tracer=tracer)
    text = tracer.counters_text()
    assert "serving_spec_tokens_drafted_total" in text
    assert "serving_spec_tokens_accepted_total" in text
    assert "serving_spec_tokens_emitted_total" in text
    assert "serving_spec_acceptance_rate" in text
    names = {ev["name"] for ev in tracer.buffer.events}
    assert {"serve.draft", "serve.decode", "serve.emit",
            "serve.sync"} <= names


# ---------------------------------------------------------------------------
# unit semantics
# ---------------------------------------------------------------------------

def _one_hot_logits(seq, vocab=16, scale=8.0):
    """[S, V] logits whose argmax at position j is seq[j]."""
    return scale * jax.nn.one_hot(jnp.asarray(seq), vocab,
                                  dtype=jnp.float32)[None]


def test_verify_draft_greedy_accepts_matching_prefix():
    zeros = jnp.zeros((1,), jnp.int32)
    greedy = jnp.zeros((1,), jnp.float32)
    # target argmaxes [5, 6, 7, 9]; draft proposes [5, 6, 8]
    logits = _one_hot_logits([5, 6, 7, 9])
    draft = jnp.asarray([[5, 6, 8, 0]], jnp.int32)
    n_acc, toks, lps = verify_draft(logits, draft, jnp.asarray([3]),
                                    greedy, zeros, zeros, zeros)
    assert int(n_acc[0]) == 2                 # d1, d2 accepted; d3 rejected
    # emitted burst = accepted drafts + the correction token, each the
    # position's argmax — exactly the sequential greedy stream
    assert toks[0, :3].tolist() == [5, 6, 7]
    assert np.all(np.asarray(lps[0, :3]) <= 0)


def test_verify_draft_greedy_full_acceptance_gets_bonus():
    zeros = jnp.zeros((1,), jnp.int32)
    greedy = jnp.zeros((1,), jnp.float32)
    logits = _one_hot_logits([5, 6, 7, 9])
    draft = jnp.asarray([[5, 6, 7, 0]], jnp.int32)
    n_acc, toks, _ = verify_draft(logits, draft, jnp.asarray([3]),
                                  greedy, zeros, zeros, zeros)
    assert int(n_acc[0]) == 3
    assert toks[0, :4].tolist() == [5, 6, 7, 9]   # 3 drafts + bonus


def test_verify_draft_stochastic_is_valid_and_deterministic():
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (4, 5, 16), jnp.float32)
    draft = jax.random.randint(key, (4, 5), 0, 16)
    n_draft = jnp.asarray([4, 2, 0, 3])
    temps = jnp.full((4,), 0.8, jnp.float32)
    zeros = jnp.zeros((4,), jnp.int32)
    seeds = jnp.asarray([1, 2, 3, 4])
    steps = jnp.asarray([0, 5, 9, 2])
    a1, t1, l1 = verify_draft(logits, draft, n_draft, temps, zeros,
                              seeds, steps)
    a2, t2, l2 = verify_draft(logits, draft, n_draft, temps, zeros,
                              seeds, steps)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    for i in range(4):
        assert 0 <= int(a1[i]) <= int(n_draft[i])
    assert np.all((np.asarray(t1) >= 0) & (np.asarray(t1) < 16))
    assert np.all(np.asarray(l1) <= 1e-6)


def test_ngram_proposer():
    p = NGramProposer(2)
    #           0  1  2  3  4  5  6
    seq = [3, 4, 9, 8, 7, 3, 4]
    # suffix [3, 4] matched at position 0 -> continuation [9, 8, 7]
    assert p.propose(seq, 3) == [9, 8, 7]
    assert p.propose(seq, 2) == [9, 8]
    assert p.propose([1, 2, 3], 3) == []          # no earlier occurrence
    assert p.propose([1], 3) == []                # shorter than the suffix
    assert p.propose(seq, 0) == []


def test_spec_verify_reserve_counts_running_only():
    def req(i, status, draft_k=0):
        r = Request(request_id=i, prompt=[1, 2],
                    sampling=SamplingParams(max_new_tokens=4))
        r.status = status
        r.draft_k = draft_k
        return r

    running = {0: req(0, Status.RUNNING, 4),      # 4 + 1
               1: req(1, Status.RUNNING),         # default_k 3 + 1
               2: req(2, Status.PREFILLING, 8)}   # not decoding: no charge
    assert spec_verify_reserve(running, 3) == 9
    assert spec_verify_reserve({}, 3) == 0


def test_adaptive_k_walks_with_acceptance(dense_params, other_params):
    prompts = _prompts(3, 16)
    up = SpeculativeConfig(k=2, min_k=1, max_k=8, method="model",
                           params=dense_params, cfg=CFG)
    _, reqs = _run(dense_params, prompts, gen=16, max_len=64, draft=up)
    assert all(r.draft_k > 2 for r in reqs), \
        "full acceptance must grow draft_k"
    down = SpeculativeConfig(k=4, min_k=1, max_k=8, method="model",
                             params=other_params, cfg=CFG)
    _, reqs = _run(dense_params, prompts, gen=16, max_len=64, draft=down)
    assert any(r.draft_k < 4 for r in reqs), \
        "majority rejection must shrink draft_k"


def test_draft_validation_errors(dense_params):
    with pytest.raises(ValueError, match="method"):
        SpeculativeConfig(method="oracle")
    with pytest.raises(ValueError, match="params"):
        SpeculativeConfig(method="model")
    with pytest.raises(ValueError, match="min_k"):
        SpeculativeConfig(k=9, method="ngram")
    bad_vocab = dataclasses.replace(CFG, vocab=CFG.vocab * 2)
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(CFG, dense_params, n_slots=2, max_len=32,
                      draft=SpeculativeConfig(
                          method="model", params=dense_params,
                          cfg=bad_vocab))


def test_verify_bucket_ladder_anchors_at_configured_k():
    from repro.serving import verify_bucket
    # draft-free steps (ngram found nothing) keep the decode shape
    assert verify_bucket(1, 4) == 1
    # any drafted step in [1, k0] shares ONE compiled shape...
    assert [verify_bucket(q, 4) for q in (2, 3, 4, 5)] == [8, 8, 8, 8]
    # ...and adaptive excursions above k0 add log2(max_k/k0) rungs
    assert verify_bucket(9, 4) == 16
    # k0=1 (the adaptive self-draft test's config) keeps the old ladder
    assert [verify_bucket(q, 1) for q in (1, 2, 3, 5, 9)] == [1, 2, 4, 8, 16]


def test_ngram_variable_draft_len_variants_bucketed(dense_params):
    """N-gram proposals run 0..k tokens per lane per step — the exact
    workload that retraced the verify step once per draft-length bucket
    (9 ``step`` variants in the serving bench) before the ladder was
    anchored at the configured k.  Periodic prompts make the proposer
    actually fire at varying match lengths; the compiled step variants
    must stay within the same bound as the adaptive-k model-draft test."""
    tracer = ServingTracer()
    draft = SpeculativeConfig(k=4, min_k=1, max_k=8, method="ngram")
    # period-4 token loops with varying phase: suffix lookup hits with
    # continuation lengths all over [0, k]
    prompts = [([5, 6, 7, 8] * 6)[:16 + i] for i in range(3)]
    engine, reqs = _run(dense_params, prompts, gen=24, max_len=64,
                        draft=draft, tracer=tracer)
    assert all(r.status is Status.FINISHED for r in reqs)
    assert engine.n_drafted > 0, "ngram proposer never fired"
    n_step = engine.adapter._step_fn._cache_size()
    assert 1 <= n_step <= 6, n_step
