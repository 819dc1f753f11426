"""Mesh-native serving: placement + tensor-parallel engine parity.

The load-bearing property (ISSUE 3 acceptance): on a 1x8 model-axis mesh
the engine produces token streams IDENTICAL to the single-device engine
for the same requests and seeds — dense and 8:16+outlier compressed
weights, slot and paged KV layouts, including prefix-cache hits and
preemption/resume — while every SparseWeight leaf and both KV arenas
carry a non-replicated NamedSharding.

The multi-device tests need forced host devices and skip otherwise; run

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m pytest tests/test_mesh_serving.py

(CI runs exactly this in its multi-device step.)  The placement-unit
tests at the bottom run on any device count.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding

from repro import configs
from repro.core import SparsifyConfig
from repro.models import get_model
from repro.models.sparse_serving import SparseWeight
from repro.serving import (SamplingParams, ServingEngine, ServingPlacement,
                           Status)

# 8 KV heads so the KV arenas and attention projections divide the 8-wide
# model axis (the GQA-narrower-than-mesh regime replicates by design)
CFG = dataclasses.replace(configs.get_smoke("llama-paper"),
                          name="mesh-serving-test", n_layers=2, d_model=128,
                          n_heads=8, n_kv_heads=8, head_dim=16, d_ff=256,
                          vocab=512, remat=False)
GEN = 5
BS = 8

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


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


def _run(params, prompts, gen, *, samplings=None, mesh=None, **kw):
    engine = ServingEngine(CFG, params, mesh=mesh, **kw)
    samplings = samplings or [SamplingParams(max_new_tokens=gen)] * len(prompts)
    reqs = [engine.submit(p, s) for p, s in zip(prompts, samplings)]
    engine.run()
    assert all(r.status is Status.FINISHED for r in reqs)
    return engine, [r.tokens for r in reqs]


def _solo(params, prompt, gen):
    _, (toks,) = _run(params, [prompt], gen, n_slots=1, max_len=64)
    return toks


# --------------------------------------------------------------------------
# parity: sharded == single-device, all weight/KV combinations
# --------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
@pytest.mark.parametrize("which", ["dense", "sparse"])
def test_mesh_engine_token_identical(which, kv_layout, mesh, dense_params,
                                     sparse_params):
    """Greedy AND seeded-stochastic streams survive sharding bit-for-bit."""
    params = dense_params if which == "dense" else sparse_params
    prompts = _prompts(3, 12)
    samplings = [SamplingParams(max_new_tokens=GEN),
                 SamplingParams(max_new_tokens=GEN),
                 SamplingParams(max_new_tokens=GEN, temperature=1.0,
                                top_k=8, seed=5)]
    kw = dict(n_slots=4, max_len=32, kv_layout=kv_layout, block_size=BS,
              samplings=samplings)
    _, ref = _run(params, prompts, GEN, **kw)
    engine, out = _run(params, prompts, GEN, mesh=mesh, **kw)
    assert out == ref
    assert engine.placement.active
    assert engine.stats()["placement"]["devices"] == 8


@needs8
def test_mesh_prefix_cache_hits_token_identical(mesh, dense_params):
    """Prefix-cache suffix prefill through the sharded gather path."""
    sys_prompt = _prompts(1, 3 * BS, seed=5)[0]
    tails = _prompts(3, 6, seed=6)
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=64,
                           kv_layout="paged", block_size=BS, mesh=mesh)
    reqs = []
    for tail in tails:                    # sequential so the cache is warm
        reqs.append(engine.submit(sys_prompt + tail,
                                  SamplingParams(max_new_tokens=GEN)))
        engine.run()
    assert engine.pool.prefix_cache.stats()["hit_tokens"] >= 2 * 3 * BS
    for tail, r in zip(tails, reqs):
        assert r.tokens == _solo(dense_params, sys_prompt + tail, GEN)


@needs8
def test_mesh_preemption_resumes_identically(mesh, dense_params):
    """Preempt-to-queue + re-prefill resume on the sharded arena."""
    prompts = _prompts(4, 16, seed=9)
    kw = dict(n_slots=4, max_len=40, kv_layout="paged", block_size=BS,
              n_blocks=10, prefix_caching=False)
    engine, out = _run(dense_params, prompts, 12, mesh=mesh, **kw)
    assert engine.n_preemptions > 0
    for p, toks in zip(prompts, out):
        assert toks == _solo(dense_params, p, 12)


# --------------------------------------------------------------------------
# placement assertions: what actually lives where
# --------------------------------------------------------------------------

@needs8
def test_sparse_leaves_carry_nonreplicated_shardings(mesh, sparse_params):
    engine = ServingEngine(CFG, sparse_params, n_slots=2, max_len=32,
                           mesh=mesh)
    n_sw = 0
    for leaf in jax.tree.leaves(
            engine.params,
            is_leaf=lambda x: isinstance(x, SparseWeight)):
        if not isinstance(leaf, SparseWeight):
            continue
        n_sw += 1
        for arr in jax.tree.leaves(leaf):       # nm/o values+meta (+scale)
            assert isinstance(arr.sharding, NamedSharding)
            assert not arr.sharding.is_fully_replicated, arr.shape
            assert "model" in jax.tree.leaves(tuple(arr.sharding.spec))
    assert n_sw > 0


@needs8
@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_kv_arenas_sharded_tables_host_side(kv_layout, mesh, dense_params):
    engine = ServingEngine(CFG, dense_params, n_slots=2, max_len=32,
                           kv_layout=kv_layout, block_size=BS, mesh=mesh)
    for arena in (engine.pool.k, engine.pool.v):
        assert isinstance(arena.sharding, NamedSharding)
        assert not arena.sharding.is_fully_replicated
        assert arena.sharding.spec[3] == "model"      # KV-head dim
    if kv_layout == "paged":
        # scheduling state stays host-side numpy, layout-agnostic
        assert isinstance(engine.pool._bt_np, np.ndarray)
        assert isinstance(engine.pool._pos_np, np.ndarray)
        assert isinstance(engine.pool.blocks.ref, np.ndarray)
        assert engine.pool.prefix_cache is not None


@needs8
def test_param_shardings_sparse_alignment_on_mesh(dense_params, sparse_params):
    """In-dim (fsdp) sharding of compressed leaves only on block-aligned
    boundaries — checked at the NamedSharding level on a 2x4 mesh."""
    from repro.parallel.sharding import sparse_weight_shardings
    mesh24 = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    sw = next(l for l in jax.tree.leaves(
        sparse_params, is_leaf=lambda x: isinstance(x, SparseWeight))
        if isinstance(l, SparseWeight))
    sh = sparse_weight_shardings(mesh24, sw)
    vals, meta = sh.nm_values, sh.nm_meta
    assert isinstance(vals, NamedSharding) and not vals.is_fully_replicated
    # values and metadata co-shard
    assert tuple(vals.spec) == tuple(meta.spec)
    # serving policy: out-dim only, contraction dims replicated
    ssh = sparse_weight_shardings(mesh24, sw, serving=True)
    assert tuple(ssh.nm_values.spec)[-1] is None


# --------------------------------------------------------------------------
# placement units (any device count — covered by plain tier-1 too)
# --------------------------------------------------------------------------

def test_inactive_placement_is_identity():
    pl = ServingPlacement()
    assert not pl.active
    assert pl.replicated is None and pl.kv is None
    x = jnp.ones((3,))
    assert pl.place_kv(x) is x and pl.place_replicated(x) is x
    tree = {"a": x}
    assert pl.place_params(tree) is tree
    assert pl.param_shardings(tree) is None
    assert pl.describe() == {"devices": 1, "mesh": None}


def test_placement_validates_mesh_and_cfg():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with pytest.raises(ValueError, match="cfg"):
        ServingPlacement(mesh)                    # mesh without cfg
    bad = jax.make_mesh((1,), ("replica",),
                        axis_types=(AxisType.Auto,))
    with pytest.raises(ValueError, match="model"):
        ServingPlacement(bad, CFG)


@needs8
def test_placement_rejects_data_parallel_mesh(dense_params):
    """Only model-axis TP is placed today; a data>1 mesh would run fully
    redundant replicas and skew per-device throughput accounting."""
    mesh24 = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with pytest.raises(ValueError, match="size 1"):
        ServingEngine(CFG, dense_params, n_slots=2, max_len=32, mesh=mesh24)


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_serving_mesh_1x1_token_identical(kv_layout, dense_params):
    """``make_serving_mesh("1x1")`` on one device: the placement path
    (explicit shardings on every jitted step over an Auto-axis mesh)
    serves the meshless engine's tokens.  The 8-device parity tests skip
    on one device; this one runs everywhere."""
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh("1x1")
    assert all(t == AxisType.Auto for t in mesh.axis_types)
    prompts = _prompts(3, 12)
    kw = dict(n_slots=4, max_len=32, kv_layout=kv_layout, block_size=BS)
    _, ref = _run(dense_params, prompts, GEN, **kw)
    engine, out = _run(dense_params, prompts, GEN, mesh=mesh, **kw)
    assert engine.placement.active
    assert engine.stats()["placement"]["devices"] == 1
    assert out == ref


def test_serve_cli_mesh_1x1(monkeypatch, tmp_path):
    """``--mesh 1x1`` serves on one CPU device, token-identical to the
    CLI without a mesh."""
    from repro.launch.serve import main
    # a set variable keeps main() from pointing this process's compile
    # cache at the repository
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    argv = ["--arch", "internlm2-1.8b", "--smoke-arch", "--batch", "2",
            "--prompt-len", "12", "--gen", "3"]
    np.testing.assert_array_equal(np.asarray(main(argv + ["--mesh", "1x1"])),
                                  np.asarray(main(argv)))


def test_engine_without_mesh_unchanged(dense_params):
    """mesh=None is the exact pre-placement engine (default path)."""
    engine, out = _run(dense_params, _prompts(2, 10), 3,
                       n_slots=2, max_len=32)
    assert not engine.placement.active
    assert engine.stats()["placement"] == {"devices": 1, "mesh": None}
    assert all(len(t) == 3 for t in out)


def test_parse_mesh_spec():
    from repro.launch.mesh import make_serving_mesh, parse_mesh_spec
    assert parse_mesh_spec(None) is None and parse_mesh_spec("") is None
    assert parse_mesh_spec("1x8") == (1, 8)
    assert parse_mesh_spec("8") == (1, 8)
    assert parse_mesh_spec("2x4") == (2, 4)
    with pytest.raises(ValueError):
        parse_mesh_spec("2x3x4")
    with pytest.raises(ValueError):
        parse_mesh_spec("banana")
    with pytest.raises(ValueError):
        parse_mesh_spec("0x8")
    with pytest.raises(ValueError):
        parse_mesh_spec("-1x8")
    assert make_serving_mesh(None) is None
    with pytest.raises(ValueError, match="devices"):
        make_serving_mesh(f"{4096}")              # more than any host has
