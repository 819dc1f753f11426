"""Quantized int8 KV arenas + int8 N:M kernel path (ISSUE 9).

Load-bearing properties:
  1. ``kv_dtype="int8"`` engines (slot and paged, dense and 8:16+outlier
     compressed weights) generate greedy streams whose divergence from
     the bf16 reference is bounded — quantization is a numerics knob,
     never a correctness break (requests finish, streams are full
     length).
  2. Quantized arenas are EXACT under every lifecycle path that re-reads
     written KV: prefix-cache hits, preemption/resume, speculative
     verify-rollback, and a 1x8 tensor-parallel mesh each reproduce the
     cold int8 engine's streams token for token (the stored int8 bytes +
     scales are the sequence's KV; re-reading them cannot drift).
  3. The compiled int8 step accesses FEWER bytes than the bf16 step at
     identical shapes: the online-softmax dequant fuses into attention,
     so no bf16 copy of the arena ever materializes in HBM (tentpole
     cost pin, same method as the cursor-independence test of ISSUE 5).
  4. Pool stats bill the arena honestly: values + scales, dtype
     labelled, on SlotKVPool.stats / BlockPool.occupancy /
     engine.stats()["pool"] (satellite).
  5. The fused int8 weight kernel (fused_sparse_linear, with and without
     outliers, with a scale operand) matches the portable dequantizing
     reference, and the int8 pallas path accesses fewer bytes than the bf16 one — the
     pre-kernel densify is structurally gone.
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
from repro.serving import (SamplingParams, ServingEngine, SpeculativeConfig,
                           Status)
from repro.serving.cache_pool import SlotKVPool, quantize_kv
from repro.serving.paged import BlockPool, PagedKVPool

CFG = dataclasses.replace(configs.get_smoke("llama-paper"),
                          name="kv-quant-test", n_layers=2, d_model=128,
                          n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
                          vocab=512, remat=False)
GEN = 8
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


def _run(params, prompts, gen=GEN, **kw):
    engine = ServingEngine(CFG, params, **kw)
    reqs = [engine.submit(p, SamplingParams(max_new_tokens=gen))
            for p in prompts]
    engine.run()
    assert all(r.status is Status.FINISHED for r in reqs)
    return engine, [r.tokens for r in reqs]


def _agreement(ref, got):
    matched = sum(sum(a == b for a, b in zip(r, g))
                  for r, g in zip(ref, got))
    total = sum(len(r) for r in ref)
    return matched / total


# --------------------------------------------------------------------------
# quantize_kv unit properties
# --------------------------------------------------------------------------

def test_quantize_kv_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 5, 4, 32),
                          jnp.float32) * 3.0
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    deq = q.astype(jnp.float32) * s[..., None]
    # absmax symmetric quant: error <= scale/2 = absmax/254 per element
    bound = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 254.0 + 1e-7
    assert bool(jnp.all(jnp.abs(deq - x) <= bound))


def test_quantize_kv_zero_rows_safe():
    x = jnp.zeros((2, 3, 2, 16), jnp.float32)
    q, s = quantize_kv(x)
    assert bool(jnp.all(q == 0)) and bool(jnp.all(s == 1.0))
    assert bool(jnp.all(jnp.isfinite(s)))


# --------------------------------------------------------------------------
# 1. bounded greedy divergence, dense/sparse x slot/paged
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
@pytest.mark.parametrize("which", ["dense", "sparse"])
def test_int8_greedy_divergence_bounded(which, kv_layout, dense_params,
                                        sparse_params):
    params = dense_params if which == "dense" else sparse_params
    prompts = _prompts(4, 12)
    kw = dict(n_slots=4, max_len=48, kv_layout=kv_layout, block_size=BS)
    _, ref = _run(params, prompts, **kw, kv_dtype="bf16")
    _, got = _run(params, prompts, **kw, kv_dtype="int8")
    assert all(len(g) == len(r) for g, r in zip(got, ref))
    agree = _agreement(ref, got)
    assert agree >= 0.6, \
        f"int8 KV diverged too far from bf16: agreement {agree:.2f}"


# --------------------------------------------------------------------------
# 2. exactness under KV-re-reading lifecycle paths
# --------------------------------------------------------------------------

def test_int8_prefix_cache_hits_token_identical(dense_params):
    """A prefix-cache hit reuses the stored int8 blocks + scales instead
    of re-prefilling; since the stored bytes ARE the sequence's KV, the
    hit path must match the cold int8 path exactly."""
    sys_prompt = _prompts(1, 3 * BS, seed=5)[0]
    tails = _prompts(3, 6, seed=6)
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=64,
                           kv_layout="paged", block_size=BS,
                           kv_dtype="int8")
    reqs = []
    for tail in tails:                    # sequential so the cache is warm
        reqs.append(engine.submit(sys_prompt + tail,
                                  SamplingParams(max_new_tokens=GEN)))
        engine.run()
    assert engine.pool.prefix_cache.stats()["hit_tokens"] >= 2 * 2 * BS
    for tail, r in zip(tails, reqs):
        _, (solo,) = _run(dense_params, [sys_prompt + tail], n_slots=1,
                          max_len=64, kv_layout="paged", block_size=BS,
                          kv_dtype="int8")
        assert r.tokens == solo, "prefix hit diverged under int8 KV"


def test_int8_preemption_resume_token_identical(dense_params):
    """Preempt/resume re-prefills from the prefix cache + deterministic
    requantization of the same fresh KV — identical int8 bytes, identical
    streams."""
    prompts = _prompts(4, 16, seed=9)
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=40,
                           kv_layout="paged", block_size=BS, n_blocks=10,
                           token_budget=16, kv_dtype="int8")
    reqs = [engine.submit(p, SamplingParams(max_new_tokens=12))
            for p in prompts]
    engine.run()
    assert all(r.status is Status.FINISHED for r in reqs)
    assert engine.n_preemptions > 0, "scenario must actually preempt"
    for p, r in zip(prompts, reqs):
        _, (solo,) = _run(dense_params, [p], gen=12, n_slots=1, max_len=40,
                          kv_layout="paged", block_size=BS,
                          kv_dtype="int8")
        assert r.tokens == solo, "preempt/resume diverged under int8 KV"


def test_int8_speculative_rollback_token_identical(dense_params,
                                                   sparse_params):
    """Verify-rollback under int8: rejected draft positions are hidden by
    the cursor and overwritten by the next deterministic requantized
    write, so the speculative engine (draft arena int8 too) is
    token-identical to the non-speculative int8 engine."""
    prompts = _prompts(4, 12, seed=11)
    kw = dict(n_slots=4, max_len=48, kv_layout="paged", block_size=BS,
              kv_dtype="int8")
    _, ref = _run(dense_params, prompts, gen=10, **kw)
    draft = SpeculativeConfig(k=3, method="model", params=sparse_params,
                              cfg=CFG)
    engine, got = _run(dense_params, prompts, gen=10, **kw, draft=draft)
    assert engine.spec.drafter.adapter.pool.kv_dtype == "int8"
    assert engine.n_drafted > 0
    assert got == ref, "speculative int8 engine diverged from baseline"


# --------------------------------------------------------------------------
# 3. tentpole cost pin: no bf16 arena materialization
# --------------------------------------------------------------------------

def test_int8_step_accesses_fewer_bytes_than_bf16(dense_params):
    """Arena-dominant shapes: the compiled int8 chunk step must touch
    FEWER HBM bytes than the bf16 step — the dequant fuses into the
    attention upcast.  A materialized bf16 copy of the arena would make
    the int8 step's bytes a superset of bf16's and fail this
    directionally."""
    costs = {}
    B, S, ML = 4, 16, 512                 # arena >> activations
    tokens = jnp.zeros((B, S), jnp.int32)
    n_new = jnp.full((B,), S, jnp.int32)
    cur = jnp.zeros((B,), jnp.int32)
    for dtype in ("bf16", "int8"):
        engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=ML,
                               kv_dtype=dtype, token_budget=16)
        lanes = jnp.asarray(engine.pool.lane_rows([0, 1, 2, 3], B))
        p = engine.pool
        arenas = ((p.k, p.v) if dtype == "bf16"
                  else (p.k, p.v, p.k_scale, p.v_scale))
        lowered = engine._step_fn.lower(engine.params, *arenas, lanes, cur,
                                        n_new, tokens)
        costs[dtype] = cost_summary(lowered.compile())["bytes_accessed"]
    assert costs["int8"] < costs["bf16"], (
        f"int8 step accessed {costs['int8']} bytes >= bf16's "
        f"{costs['bf16']}: a dense arena copy is materializing")


# --------------------------------------------------------------------------
# 4. satellite: stats bill values + scales with dtype labels
# --------------------------------------------------------------------------

def test_slot_pool_stats_bytes():
    L, KV, hd = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    ns, ml = 4, 64
    val_elems = L * ns * ml * KV * hd
    sc_elems = L * ns * ml * KV
    bf = SlotKVPool(CFG, n_slots=ns, max_len=ml)
    q = SlotKVPool(CFG, n_slots=ns, max_len=ml, kv_dtype="int8")
    sb, sq = bf.stats(), q.stats()
    assert sb["kv_dtype"] == "bf16" and sq["kv_dtype"] == "int8"
    assert sb["scale_bytes"] == 0
    assert sb["arena_bytes"] == 2 * val_elems * 2          # k+v, bf16
    assert sq["scale_bytes"] == 2 * sc_elems * 4           # k+v, f32
    assert sq["arena_bytes"] == 2 * val_elems + sq["scale_bytes"]
    assert sq["arena_bytes"] < sb["arena_bytes"]


def test_block_pool_occupancy_bytes():
    L, KV, hd = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    nb = 8
    val_elems = L * nb * BS * KV * hd
    sc_elems = L * nb * BS * KV
    bf = BlockPool(CFG, n_blocks=nb, block_size=BS)
    q = BlockPool(CFG, n_blocks=nb, block_size=BS, kv_dtype="int8")
    ob, oq = bf.occupancy(), q.occupancy()
    assert ob["kv_dtype"] == "bf16" and oq["kv_dtype"] == "int8"
    assert ob["scale_bytes"] == 0
    assert ob["arena_bytes"] == 2 * val_elems * 2
    assert oq["scale_bytes"] == 2 * sc_elems * 4
    assert oq["arena_bytes"] == 2 * val_elems + oq["scale_bytes"]


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_engine_stats_surface_arena_bytes(kv_layout, dense_params):
    engine = ServingEngine(CFG, dense_params, n_slots=2, max_len=32,
                           kv_layout=kv_layout, block_size=BS,
                           kv_dtype="int8")
    st = engine.stats()
    assert st["kv_dtype"] == "int8"
    pool = st["pool"]
    assert pool["kv_dtype"] == "int8"
    assert pool["arena_bytes"] > 0
    assert pool["scale_bytes"] > 0
    assert pool["scale_bytes"] < pool["arena_bytes"]


# --------------------------------------------------------------------------
# 5. int8 weight kernels: parity with the portable path, no densify
# --------------------------------------------------------------------------

@pytest.mark.parametrize("outliers", [None, "16:256"])
def test_int8_kernel_matches_portable(outliers):
    from repro.models.sparse_serving import (_to_sparse_weight,
                                             sparse_apply,
                                             sparse_apply_pallas)
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 512)) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 512), jnp.float32)
    cfg = SparsifyConfig(scorer="magnitude", use_smoothquant=False,
                         outlier_pattern=outliers)
    sw = _to_sparse_weight(w, cfg, quantize=True)
    assert sw.nm_values.dtype == jnp.int8 and sw.v_scale is not None
    assert (sw.o_values is None) == (outliers is None)
    y_ref = sparse_apply(sw, x)           # portable: dequant then matmul
    y_pal = sparse_apply_pallas(sw, x)    # fused: dequant in-register
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-5, atol=2e-4)


def test_int8_kernel_accesses_fewer_bytes_than_bf16():
    """The acceptance pin for deleting the pre-kernel densify: the
    compiled int8 apply must read FEWER bytes than the bf16 apply (int8
    values are half the bytes; a pre-kernel dequantize-to-bf16 would
    read at least as many)."""
    from repro.models.sparse_serving import (_to_sparse_weight,
                                             sparse_apply_pallas)
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 512)) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 512), jnp.bfloat16)
    cfg = SparsifyConfig(scorer="magnitude", use_smoothquant=False)
    costs = {}
    for quant in (False, True):
        sw = _to_sparse_weight(w, cfg, quantize=quant)
        # the weight is an argument, so its buffers count as bytes read
        compiled = jax.jit(sparse_apply_pallas).lower(sw, x).compile()
        costs[quant] = cost_summary(compiled)["bytes_accessed"]
    assert costs[True] < costs[False], (
        f"int8 apply accessed {costs[True]} bytes >= bf16's "
        f"{costs[False]}: values are being densified before the kernel")


# --------------------------------------------------------------------------
# 6. mesh: int8 arenas + co-sharded scales under tensor parallelism
# --------------------------------------------------------------------------

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

MESH_CFG = dataclasses.replace(CFG, name="kv-quant-mesh-test", n_heads=8,
                               n_kv_heads=8, head_dim=16)


@needs8
@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_mesh_int8_token_identical(kv_layout):
    """Sharded int8 engine == single-device int8 engine, token for token:
    the scale arenas co-shard with the KV-head dim so the dequant is
    local to each shard."""
    params = get_model(MESH_CFG).init(jax.random.PRNGKey(0))
    prompts = [t.tolist() for t in
               jax.random.randint(jax.random.PRNGKey(2), (3, 12), 0,
                                  MESH_CFG.vocab)]

    def run(mesh):
        engine = ServingEngine(MESH_CFG, params, n_slots=4, max_len=48,
                               kv_layout=kv_layout, block_size=BS,
                               kv_dtype="int8", mesh=mesh)
        reqs = [engine.submit(p, SamplingParams(max_new_tokens=5))
                for p in prompts]
        engine.run()
        assert all(r.status is Status.FINISHED for r in reqs)
        return [r.tokens for r in reqs]

    mesh = jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    assert run(mesh) == run(None)
