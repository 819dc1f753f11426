"""Chunked prefill through the token-budgeted step pipeline.

Load-bearing properties (ISSUE 4 acceptance):
  1. The chunked engine (token_budget smaller than the longest prompt) is
     TOKEN-IDENTICAL to the one-shot engine and to the legacy lock-step
     loop — dense and 8:16+outlier compressed weights, slot and paged KV
     layouts, prefix-cache hits and preemption/resume included, and on a
     1x8 mesh.  Chunking is a scheduling change, never a numerics change.
  2. The token budget is a hard per-step bound: no step's prefill work
     exceeds it, in-flight cursors advance before new admissions, and the
     FIFO queue head is never skipped (no starvation of long prompts).
  3. Preempted requests resume from the last fully-written block (their
     blocks are published to the prefix cache at preemption), not by
     recomputing prompt + generated from scratch.

Uses ``hypothesis`` when installed, else the deterministic fallback sweep
(tests/hypothesis_fallback.py) for the scheduler property walk.
"""
import dataclasses
import random

import jax
from jax.sharding import AxisType
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                               # pragma: no cover
    from hypothesis_fallback import given, settings, st

from repro import configs
from repro.core import SparsifyConfig
from repro.models import get_model
from repro.serving import SamplingParams, ServingEngine, Status
from repro.serving.scheduler import (CHUNK_QUANTUM, plan_chunks,
                                     resolve_token_budget)

CFG = dataclasses.replace(configs.get_smoke("llama-paper"),
                          name="chunked-test", n_layers=2, d_model=128,
                          n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
                          vocab=512, remat=False)
GEN = 6
BS = 8                                     # paged block size


@pytest.fixture(scope="module")
def dense_params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


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


def _run_budgeted(params, prompts, gen, **kw):
    """Run an engine to drain, asserting the per-step budget bound."""
    engine = ServingEngine(CFG, params, **kw)
    reqs = [engine.submit(p, SamplingParams(max_new_tokens=gen))
            for p in prompts]
    while engine.has_work:
        stats = engine.step()
        assert stats["prefill_tokens"] <= engine.token_budget
    assert all(r.status is Status.FINISHED for r in reqs)
    return engine, reqs


def _solo(params, prompt, gen):
    _, (r,) = _run_budgeted(params, [prompt], gen, n_slots=1, max_len=64)
    return r.tokens


# --------------------------------------------------------------------------
# token identity: chunked == one-shot, all weight/layout combinations
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
@pytest.mark.parametrize("which", ["dense", "sparse"])
def test_chunked_token_identical_to_oneshot(which, kv_layout, dense_params,
                                            sparse_params):
    params = dense_params if which == "dense" else sparse_params
    prompts = _prompts(4, 24)
    # one-shot: budget covers any prompt whole
    _, ref = _run_budgeted(params, prompts, GEN, n_slots=4, max_len=40,
                           kv_layout=kv_layout, block_size=BS,
                           token_budget=4 * 40)
    # chunked: a 24-token prompt takes 3 chunks of 8
    engine, reqs = _run_budgeted(params, prompts, GEN, n_slots=4, max_len=40,
                                 kv_layout=kv_layout, block_size=BS,
                                 token_budget=8)
    for i, (a, b) in enumerate(zip(reqs, ref)):
        assert a.tokens == b.tokens, f"request {i} diverged under chunking"
    assert all(r.metrics.prefill_chunks >= 3 for r in reqs)
    assert all(r.metrics.ttft >= 0 for r in reqs)


def test_chunked_mixed_arrivals_decode_keeps_flowing(dense_params):
    """A long prompt lands while short requests decode: the prompt takes
    several steps (budget-bounded) and the short requests emit a token on
    every one of those steps — the anti-stall property chunking buys."""
    shorts = _prompts(2, 8, seed=3)
    long_p = _prompts(1, 32, seed=4)[0]
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=48,
                           token_budget=16)
    short_reqs = [engine.submit(p, SamplingParams(max_new_tokens=12))
                  for p in shorts]
    engine.step()                          # both shorts (8+8) + first tokens
    assert all(r.status is Status.RUNNING for r in short_reqs)
    long_req = engine.submit(long_p, SamplingParams(max_new_tokens=4))
    emitted_during_long_prefill = []
    while long_req.status in (Status.QUEUED, Status.PREFILLING):
        before = [len(r.tokens) for r in short_reqs]
        stats = engine.step()
        assert stats["prefill_tokens"] <= 16
        emitted_during_long_prefill.append(
            [len(r.tokens) - b for r, b in zip(short_reqs, before)])
    # the 32-token prompt needed 2 budgeted steps, and every one of them
    # also advanced the decoding shorts (no monopolized step)
    assert long_req.metrics.prefill_chunks == 2
    assert all(all(d == 1 for d in step_d)
               for step_d in emitted_during_long_prefill)
    engine.run()
    assert long_req.tokens == _solo(dense_params, long_p, 4)
    for p, r in zip(shorts, short_reqs):
        assert r.tokens == _solo(dense_params, p, 12)


def test_chunked_prefix_cache_hits_token_identical(dense_params):
    """Chunked prefill composes with prefix-cache hits: the cursor starts
    at the cached block boundary and chunks cover only the remainder."""
    sys_prompt = _prompts(1, 3 * BS, seed=5)[0]
    tails = _prompts(3, 6, seed=6)
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=64,
                           kv_layout="paged", block_size=BS, token_budget=8)
    reqs = []
    for tail in tails:                    # sequential so the cache is warm
        reqs.append(engine.submit(sys_prompt + tail,
                                  SamplingParams(max_new_tokens=GEN)))
        engine.run()
    stats = engine.pool.prefix_cache.stats()
    assert stats["hit_tokens"] >= 2 * 2 * BS
    # cache-hit requests prefilled fewer chunks than the cold one
    assert reqs[1].metrics.prefill_chunks < reqs[0].metrics.prefill_chunks
    for tail, r in zip(tails, reqs):
        assert r.tokens == _solo(dense_params, sys_prompt + tail, GEN)


# --------------------------------------------------------------------------
# preemption: cursor resume from the last fully-written block
# --------------------------------------------------------------------------

def test_preemption_resumes_from_cached_blocks(dense_params):
    """Regression (ISSUE 4 satellite): preempted requests used to
    re-prefill prompt + generated from scratch.  Now their fully-written
    blocks are published to the prefix cache at preemption and the resume
    matches them — distinct prompts mean any cache hit can only come from
    a resume.  Token streams are preserved exactly."""
    prompts = _prompts(4, 16, seed=9)
    engine, reqs = _run_budgeted(dense_params, prompts, 12, n_slots=4,
                                 max_len=40, kv_layout="paged",
                                 block_size=BS, n_blocks=10, token_budget=16)
    assert engine.n_preemptions > 0
    assert any(r.n_preempted > 0 for r in reqs)
    assert engine.pool.prefix_cache.stats()["hit_tokens"] > 0, \
        "resume did not reuse the preempted request's written blocks"
    for p, r in zip(prompts, reqs):
        assert r.tokens == _solo(dense_params, p, 12)


def test_preemption_without_cache_still_identical(dense_params):
    """With prefix caching off the resume recomputes through the chunked
    path — slower, but the streams must still match exactly."""
    prompts = _prompts(4, 16, seed=9)
    engine, reqs = _run_budgeted(dense_params, prompts, 12, n_slots=4,
                                 max_len=40, kv_layout="paged",
                                 block_size=BS, n_blocks=10,
                                 prefix_caching=False, token_budget=16)
    assert engine.n_preemptions > 0
    for p, r in zip(prompts, reqs):
        assert r.tokens == _solo(dense_params, p, 12)


def test_preemption_of_validated_chunk_same_step(dense_params):
    """Regression: with two mid-prefill prompts and nothing decoding, the
    younger one's block-capacity loop preempts the older AFTER it was
    already validated into this step's chunk plan — the stale entry (slot
    None, cursor reset) must be dropped, not run (it used to crash the
    step loop with a TypeError in the paged write path)."""
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=80,
                           kv_layout="paged", block_size=BS, n_blocks=9,
                           token_budget=24, prefix_caching=False)
    short = engine.submit([1, 2, 3], SamplingParams(max_new_tokens=5))
    engine.step()
    longs = _prompts(2, 64, seed=11)
    reqs = [engine.submit(p, SamplingParams(max_new_tokens=4))
            for p in longs]
    engine.run(max_steps=300)
    assert engine.n_preemptions > 0
    assert short.status is Status.FINISHED
    assert all(r.status is Status.FINISHED for r in reqs)
    for p, r in zip(longs, reqs):
        solo = ServingEngine(CFG, dense_params, n_slots=1, max_len=80,
                             kv_layout="paged", block_size=BS)
        s = solo.submit(p, SamplingParams(max_new_tokens=4))
        solo.run()
        assert r.tokens == s.tokens


# --------------------------------------------------------------------------
# scheduler policy: budget accounting, FIFO, no starvation
# --------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_plan_chunks_invariants(seed):
    """Random walk over the token-budget planner: per-call bounds, and a
    multi-round simulation in which every request — long prompts included
    — finishes its prefill (starvation-freedom)."""
    rng = random.Random(seed)
    Q = CHUNK_QUANTUM
    budget = rng.choice([Q, 2 * Q, 3 * Q, 8 * Q])
    n_rows = rng.randint(1, 4)
    queued = [(i, rng.randint(1, 12 * Q)) for i in range(rng.randint(1, 10))]
    in_flight: list[list] = []             # [key, remaining], admission order
    admitted_order: list[int] = []
    rounds = 0
    while queued or in_flight:
        rounds += 1
        assert rounds < 400, "scheduler starved a request"

        def try_admit(key, chunk):
            if len(in_flight) >= n_rows:          # no free row
                return None
            assert queued and queued[0][0] == key, "queue head skipped"
            _, n = queued.pop(0)
            in_flight.append([key, n])
            admitted_order.append(key)
            return n

        plan = plan_chunks([(k, rem) for k, rem in in_flight], list(queued),
                           budget, Q, try_admit)
        assert sum(t for _, t in plan) <= budget, "budget exceeded"
        seen = [k for k, _ in plan]
        assert len(seen) == len(set(seen)), "request chunked twice in a step"
        for key, take in plan:
            entry = next(e for e in in_flight if e[0] == key)
            assert 0 < take <= entry[1]
            if take < entry[1]:
                assert take % Q == 0, "mid-sequence chunk not quantized"
            entry[1] -= take
        done = [e for e in in_flight if e[1] == 0]
        # completed prefills leave their rows (decode is out of scope here)
        in_flight = [e for e in in_flight if e[1] > 0]
        if not plan and not done and len(in_flight) >= n_rows:
            # every row is mid-prefill but the budget is below the quantum
            # head-of-line requirement — impossible: budget >= Q always
            # lets the oldest in-flight advance
            raise AssertionError("no progress")
    assert admitted_order == sorted(admitted_order), "admission broke FIFO"


def test_resolve_token_budget_alias_and_floor():
    import repro.serving.scheduler as sched
    assert resolve_token_budget(64, None, 256) == 64
    assert resolve_token_budget(None, None, 256) == 512
    sched._budget_alias_warned = False
    with pytest.warns(DeprecationWarning):
        assert resolve_token_budget(None, 3, 100) == 300
    # one-time warning: a second resolution stays silent
    import warnings as w
    with w.catch_warnings():
        w.simplefilter("error")
        assert resolve_token_budget(None, 2, 100) == 200
    with pytest.raises(ValueError, match="token_budget"):
        resolve_token_budget(CHUNK_QUANTUM - 1, None, 256)


def test_deprecated_max_prefill_per_step_engine_alias(dense_params):
    import repro.serving.scheduler as sched
    sched._budget_alias_warned = False
    with pytest.warns(DeprecationWarning, match="max_prefill_per_step"):
        engine = ServingEngine(CFG, dense_params, n_slots=2, max_len=32,
                               max_prefill_per_step=2)
    assert engine.token_budget == 2 * 32
    r = engine.submit(_prompts(1, 8)[0], SamplingParams(max_new_tokens=3))
    engine.run()
    assert r.tokens == _solo(dense_params, list(r.prompt), 3)


# --------------------------------------------------------------------------
# mesh parity: chunked 1x8 == one-shot single-device
# --------------------------------------------------------------------------

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

# 8 KV heads so arenas/projections divide the 8-wide model axis
MESH_CFG = dataclasses.replace(CFG, name="chunked-mesh-test", n_heads=8,
                               n_kv_heads=8, head_dim=16)


@needs8
@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
@pytest.mark.parametrize("which", ["dense", "sparse"])
def test_mesh_chunked_token_identical(which, kv_layout):
    params = get_model(MESH_CFG).init(jax.random.PRNGKey(0))
    if which == "sparse":
        from repro.models.sparse_serving import sparsify_for_serving
        scfg = SparsifyConfig(weight_pattern="8:16", outlier_pattern="16:256",
                              scorer="magnitude", use_smoothquant=False)
        params, _ = sparsify_for_serving(params, scfg)
    prompts = [t.tolist() for t in
               jax.random.randint(jax.random.PRNGKey(2), (3, 24), 0,
                                  MESH_CFG.vocab)]

    def run(mesh, token_budget):
        engine = ServingEngine(MESH_CFG, params, n_slots=4, max_len=40,
                               kv_layout=kv_layout, block_size=BS,
                               token_budget=token_budget, mesh=mesh)
        reqs = [engine.submit(p, SamplingParams(max_new_tokens=GEN))
                for p in prompts]
        engine.run()
        assert all(r.status is Status.FINISHED for r in reqs)
        return [r.tokens for r in reqs]

    mesh = jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    ref = run(None, 4 * 40)                 # single-device, one-shot
    assert run(mesh, 8) == ref              # sharded, 3 chunks per prompt
