"""The unified attend-over-pool primitive (ISSUE 5 acceptance).

Load-bearing properties:
  1. ``transformer.unified_step`` is ONE attention path for every serving
     shape: a long prompt prefilled one-shot (S = P, cursor = 0), in
     chunks (S = chunk), or extended token-by-token through the decode
     shape (S = 1) produces the same logits and the same token streams —
     slot and paged views, dense and 8:16+outlier compressed weights, and
     on a 1x8 mesh.
  2. Chunked prefill attends IN PLACE: per-step prefix HBM traffic is
     independent of the written-prefix length (the compiled step's cost
     does not change with the cursor — the O(P^2/budget) re-gather of the
     old ``gather_prefix`` path is structurally impossible), asserted
     through ``launch/hlo_analysis.cost_summary``.
  3. The three legacy attention entry points and the prefix gathers are
     gone.
  4. ``token_budget`` is validated at engine construction (satellite):
     budgets that could never schedule a chunk raise a clear ValueError.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro import configs
from repro.core import SparsifyConfig
from repro.launch.hlo_analysis import cost_summary
from repro.models import get_model
from repro.models import transformer as tfm
from repro.serving import (PagedPoolView, SamplingParams, ServingEngine,
                           SlotPoolView, Status, validate_token_budget)

# float32 so the logits comparisons below resolve real divergence, not
# bf16 rounding between differently-shaped (but equivalent) reductions
CFG = dataclasses.replace(configs.get_smoke("llama-paper"),
                          name="attend-pool-test", n_layers=2, d_model=128,
                          n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
                          vocab=512, remat=False, dtype=jnp.float32)
BS = 8                                     # paged block size
P = 48                                     # long-prompt length
T = 64                                     # arena tokens per row


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


# --------------------------------------------------------------------------
# primitive-level parity walk: chunked == one-shot == decode-extended
# --------------------------------------------------------------------------

def _slot_walk(params, toks, chunks):
    """Feed ``toks`` [1, P] through unified_step in the given chunk sizes
    against one slot-arena row; returns per-position logits [P, V]."""
    L, KV, hd = CFG.n_layers, CFG.n_kv_heads, CFG.hd
    k = jnp.zeros((L, 1, T, KV, hd), CFG.dtype)
    v = jnp.zeros((L, 1, T, KV, hd), CFG.dtype)
    outs, cursor = [], 0
    for ln in chunks:
        view = SlotPoolView(k=k, v=v, rows=jnp.asarray([0], jnp.int32),
                            cursor=jnp.asarray([cursor], jnp.int32),
                            n_new=jnp.asarray([ln], jnp.int32))
        logits, (k, v) = tfm.unified_step(
            params, view, {"tokens": toks[:, cursor:cursor + ln]}, CFG)
        outs.append(logits[0])
        cursor += ln
    return jnp.concatenate(outs, axis=0)


def _paged_walk(params, toks, chunks):
    """Same walk over a paged view: identity block table (block 0 = trash)."""
    L, KV, hd = CFG.n_layers, CFG.n_kv_heads, CFG.hd
    nb = T // BS
    k = jnp.zeros((L, nb + 1, BS, KV, hd), CFG.dtype)
    v = jnp.zeros((L, nb + 1, BS, KV, hd), CFG.dtype)
    bt = jnp.asarray([[1 + i for i in range(nb)]], jnp.int32)
    outs, cursor = [], 0
    for ln in chunks:
        view = PagedPoolView(k=k, v=v, block_tables=bt,
                             cursor=jnp.asarray([cursor], jnp.int32),
                             n_new=jnp.asarray([ln], jnp.int32), trash=0)
        logits, (k, v) = tfm.unified_step(
            params, view, {"tokens": toks[:, cursor:cursor + ln]}, CFG)
        outs.append(logits[0])
        cursor += ln
    return jnp.concatenate(outs, axis=0)


@pytest.mark.parametrize("layout", ["slot", "paged"])
@pytest.mark.parametrize("which", ["dense", "sparse"])
def test_long_prompt_parity_walk(which, layout, dense_params, sparse_params):
    """One primitive, three shapes: S=P one-shot, S=chunk, S=1 decode —
    argmax-identical logits at every prompt position, and numerically the
    legacy full-sequence forward."""
    params = dense_params if which == "dense" else sparse_params
    toks = jnp.asarray(_prompts(1, P), jnp.int32)
    walk = _slot_walk if layout == "slot" else _paged_walk
    oneshot = walk(params, toks, [P])
    chunked = walk(params, toks, [8] * (P // 8))
    stepped = walk(params, toks, [1] * P)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(oneshot),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(stepped), np.asarray(oneshot),
                               atol=1e-4, rtol=1e-4)
    assert (jnp.argmax(chunked, -1) == jnp.argmax(oneshot, -1)).all()
    assert (jnp.argmax(stepped, -1) == jnp.argmax(oneshot, -1)).all()
    # ... and the pre-pool full-sequence forward agrees
    ref, _ = tfm.forward(params, {"tokens": toks}, CFG)
    np.testing.assert_allclose(np.asarray(oneshot), np.asarray(ref[0]),
                               atol=1e-4, rtol=1e-4)


def test_slot_and_paged_views_agree(dense_params):
    toks = jnp.asarray(_prompts(1, P, seed=3), jnp.int32)
    a = _slot_walk(dense_params, toks, [16] * (P // 16))
    b = _paged_walk(dense_params, toks, [16] * (P // 16))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# engine-level: long-prompt streams, chunked == one-shot, all combinations
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
@pytest.mark.parametrize("which", ["dense", "sparse"])
def test_engine_long_prompt_chunked_identical(which, kv_layout, dense_params,
                                              sparse_params):
    params = dense_params if which == "dense" else sparse_params
    prompts = _prompts(3, P, seed=5)

    def run(budget):
        engine = ServingEngine(CFG, params, n_slots=4, max_len=T,
                               kv_layout=kv_layout, block_size=BS,
                               token_budget=budget)
        reqs = [engine.submit(p, SamplingParams(max_new_tokens=6))
                for p in prompts]
        engine.run()
        assert all(r.status is Status.FINISHED for r in reqs)
        return [r.tokens for r in reqs], reqs

    ref, _ = run(4 * T)                       # one-shot
    out, reqs = run(16)                       # 3 chunks per prompt minimum
    assert out == ref
    assert all(r.metrics.prefill_chunks >= 3 for r in reqs)


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_sliding_window_chunked_identical(kv_layout):
    """MoE + sliding-window + GQA (mixtral smoke): the windowed in-place
    mask is chunk-size invariant on both layouts."""
    cfg = configs.get_smoke("mixtral-8x7b")
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    prompts = [t.tolist() for t in
               jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                  cfg.vocab)]

    def run(budget):
        engine = ServingEngine(cfg, params, n_slots=2, max_len=48,
                               kv_layout=kv_layout, block_size=BS,
                               token_budget=budget)
        reqs = [engine.submit(p, SamplingParams(max_new_tokens=6))
                for p in prompts]
        engine.run()
        assert all(r.status is Status.FINISHED for r in reqs)
        return [r.tokens for r in reqs]

    assert run(8) == run(2 * 48)


# --------------------------------------------------------------------------
# HBM regression: per-step prefix traffic independent of the cursor
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_chunk_step_cost_independent_of_cursor(kv_layout, dense_params):
    """The compiled chunk step reads the arena through the pool view, so
    its cost is a function of (batch, bucket) ONLY — lowering the same
    shapes at cursor 0 and at a deep cursor yields identical
    bytes-accessed (the old gather path shipped a [L, B, cursor, KV, hd]
    prefix operand whose bytes grew linearly with the cursor)."""
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=128,
                           kv_layout=kv_layout, block_size=BS,
                           token_budget=16)
    B, S = 4, 16
    tokens = jnp.zeros((B, S), jnp.int32)
    n_new = jnp.full((B,), S, jnp.int32)
    if kv_layout == "paged":
        lanes = jnp.asarray(engine.pool.lane_tables([0, 1, 2, 3], B))
    else:
        lanes = jnp.asarray(engine.pool.lane_rows([0, 1, 2, 3], B))

    def cost(cursor_val):
        cur = jnp.full((B,), cursor_val, jnp.int32)
        lowered = engine._step_fn.lower(
            engine.params, engine.pool.k, engine.pool.v, lanes, cur,
            n_new, tokens)
        c = cost_summary(lowered.compile())
        # no operand of the compiled step may scale with the cursor: the
        # only prefix-sized buffers are the arenas themselves
        for aval in jax.tree.leaves(lowered.in_avals):
            assert cursor_val not in aval.shape or cursor_val in (0, S)
        return c

    c0, c1 = cost(0), cost(96)
    assert c0["bytes_accessed"] == c1["bytes_accessed"]
    assert c0["flops"] == c1["flops"]


def test_legacy_attention_entry_points_gone():
    """ISSUE 5 acceptance: gather_prefix and the three divergent entry
    points no longer exist — attend_over_pool is the only path."""
    from repro.serving import PagedKVPool, SlotKVPool
    for name in ("forward_with_prefix", "decode_step", "decode_step_paged"):
        assert not hasattr(tfm, name), name
    for pool_cls in (SlotKVPool, PagedKVPool):
        assert not hasattr(pool_cls, "gather_prefix")
        assert not hasattr(pool_cls, "write_prefill")
        assert not hasattr(pool_cls, "write_prefill_group")
    assert callable(tfm.attend_over_pool)
    assert callable(tfm.unified_step)


# --------------------------------------------------------------------------
# satellite: token_budget validated at engine construction
# --------------------------------------------------------------------------

def test_token_budget_validated_at_construction(dense_params):
    assert validate_token_budget(8, max_len=64) == 8
    assert validate_token_budget(4, max_len=4) == 4     # tiny-pool engines
    with pytest.raises(ValueError, match="chunk quantum"):
        validate_token_budget(4, max_len=64)
    with pytest.raises(ValueError, match="max_len"):
        validate_token_budget(8, max_len=0)
    # the engine surfaces the same clear error at construction, instead
    # of a stalled plan_chunks loop deep inside step()
    with pytest.raises(ValueError, match="chunk quantum"):
        ServingEngine(CFG, dense_params, n_slots=2, max_len=64,
                      token_budget=4)
    # deprecated alias resolves, then validates, through the same path
    engine = ServingEngine(CFG, dense_params, n_slots=2, max_len=16,
                           token_budget=16)
    assert engine.token_budget == 16


# --------------------------------------------------------------------------
# mesh: the unified path is token-identical under tensor parallelism
# --------------------------------------------------------------------------

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

MESH_CFG = dataclasses.replace(CFG, name="attend-pool-mesh-test", n_heads=8,
                               n_kv_heads=8, head_dim=16)


@needs8
@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_mesh_long_prompt_unified_identical(kv_layout):
    params = get_model(MESH_CFG).init(jax.random.PRNGKey(0))
    prompts = [t.tolist() for t in
               jax.random.randint(jax.random.PRNGKey(2), (3, P), 0,
                                  MESH_CFG.vocab)]

    def run(mesh, budget):
        engine = ServingEngine(MESH_CFG, params, n_slots=4, max_len=T,
                               kv_layout=kv_layout, block_size=BS,
                               token_budget=budget, mesh=mesh)
        reqs = [engine.submit(p, SamplingParams(max_new_tokens=5))
                for p in prompts]
        engine.run()
        assert all(r.status is Status.FINISHED for r in reqs)
        return [r.tokens for r in reqs]

    mesh = jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    ref = run(None, 4 * T)                  # single-device, one-shot
    assert run(mesh, 16) == ref             # sharded, chunked
