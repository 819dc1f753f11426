"""Every model family rides the unified serving engine: token-identity
parity against each family's ``decode_lockstep`` reference, preemption
swap/resume exactness, scheduler behavior for O(1)-state families, the
``RecurrentStatePool`` lifecycle invariants, and the recurrent-state
shardings on a forced 8-device mesh.

All parity configs pin ``dtype=float32``: XLA rounds fused sub-f32
elementwise chains at shape-dependent fusion boundaries, so a bf16 engine
step (one program shape) and the bf16 one-shot loop (another) can disagree
by one ulp — enough to flip greedy argmax on a near-tie without either
side being wrong.  At f32 every elementwise op rounds identically whether
fused or not, so token streams must match bit-for-bit and any mismatch is
a real scheduling/state bug.  (serve.py's ``--legacy`` cross-check
documents the same caveat for sub-f32 runs.)

The mesh tests need forced host devices and skip otherwise; CI's
multi-device job runs

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m pytest tests/test_family_engines.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                     # pragma: no cover
    from hypothesis_fallback import given, settings, st

from repro import configs
from repro.core import SparsifyConfig
from repro.models import get_model, grow_caches
from repro.serving import (DoubleFree, RecurrentStatePool, SamplingParams,
                           ServingEngine, Status)

GEN = 6
P_LEN = 16
ARCHS = {"ssm": "xlstm-350m", "hybrid": "zamba2-2.7b",
         "encdec": "whisper-medium"}


def _cfg(family):
    cfg = configs.get_smoke(ARCHS[family])
    return dataclasses.replace(cfg, name=f"family-test-{family}",
                               dtype=jnp.float32, remat=False)


CFGS = {fam: _cfg(fam) for fam in ARCHS}


@pytest.fixture(scope="module", params=list(ARCHS))
def family(request):
    return request.param


@pytest.fixture(scope="module")
def dense_params():
    return {fam: get_model(cfg).init(jax.random.PRNGKey(0))
            for fam, cfg in CFGS.items()}


@pytest.fixture(scope="module")
def sparse_params(dense_params):
    from repro.models.sparse_serving import sparsify_for_serving
    scfg = SparsifyConfig(weight_pattern="8:16", outlier_pattern="16:256",
                          scorer="magnitude", use_smoothquant=False)
    out = {}
    for fam, params in dense_params.items():
        out[fam], report = sparsify_for_serving(params, scfg)
        assert report["n_layers_sparsified"] > 0, fam
    return out


def _prompts(cfg, n, length, seed=1):
    key = jax.random.PRNGKey(seed)
    return np.asarray(jax.random.randint(key, (n, length), 0, cfg.vocab))


def _embeds(cfg, n, length, seed=2):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed),
                                        (n, length, cfg.d_model),
                                        jnp.float32))


def _lockstep(cfg, params, prompts, gen, embeds=None):
    """The legacy one-shot loop: batched prefill + ``decode_lockstep``
    greedy decode — each family's reference float operation order."""
    zoo = get_model(cfg)
    toks = jnp.asarray(prompts, jnp.int32)
    batch = {"tokens": toks}
    if embeds is not None:
        batch["embeds"] = jnp.asarray(embeds)
    logits, caches = zoo.prefill(params, batch)
    caches = grow_caches(caches, toks.shape[1] + gen)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    outs = [tok]
    for _ in range(gen - 1):
        logits, caches = zoo.decode(params, caches, {"tokens": tok})
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        outs.append(tok)
    return np.asarray(jnp.concatenate(outs, 1))


def _submit_all(engine, cfg, prompts, gen, embeds=None):
    return [engine.submit(p, SamplingParams(max_new_tokens=gen),
                          embeds=None if embeds is None else embeds[i])
            for i, p in enumerate(prompts)]


def _ref(cfg, params, prompts, gen):
    embeds = _embeds(cfg, len(prompts), 7) if cfg.family == "encdec" else None
    return _lockstep(cfg, params, prompts, gen, embeds=embeds), embeds


# ---------------------------------------------------------------- parity ---

@pytest.mark.parametrize("which", ["dense", "sparse"])
def test_engine_matches_lockstep(family, which, dense_params, sparse_params):
    """Chunked, continuously-batched engine == one-shot lock-step loop,
    token for token, for dense and 8:16-compressed weights alike."""
    cfg = CFGS[family]
    params = (dense_params if which == "dense" else sparse_params)[family]
    prompts = _prompts(cfg, 3, P_LEN)
    ref, embeds = _ref(cfg, params, prompts, GEN)
    engine = ServingEngine(cfg, params, n_slots=4, max_len=P_LEN + GEN,
                           token_budget=8, max_ctx=7)
    reqs = _submit_all(engine, cfg, prompts, GEN, embeds)
    engine.run()
    for i, r in enumerate(reqs):
        assert r.status is Status.FINISHED
        assert r.tokens == ref[i].tolist(), f"{family}/{which} req {i}"
    assert engine.stats()["family"] == family


def test_hybrid_paged_matches_lockstep(dense_params):
    """The hybrid family mixes paged shared-attention KV with slot-indexed
    SSM state inside one step; block-granular allocation must not change a
    single token."""
    cfg = CFGS["hybrid"]
    params = dense_params["hybrid"]
    prompts = _prompts(cfg, 3, P_LEN)
    ref, _ = _ref(cfg, params, prompts, GEN)
    engine = ServingEngine(cfg, params, n_slots=4, max_len=P_LEN + GEN,
                           token_budget=8, kv_layout="paged", block_size=4)
    reqs = _submit_all(engine, cfg, prompts, GEN)
    engine.run()
    for i, r in enumerate(reqs):
        assert r.tokens == ref[i].tolist(), f"paged hybrid req {i}"
    # prefix caching is structurally off: cached KV blocks cannot
    # reconstruct the SSM state that absorbed those tokens
    assert engine.pool.prefix_cache is None


def test_preempt_resume_exact(family, dense_params):
    """Preempting a stateful request mid-generation and resuming it must
    reproduce the uninterrupted stream exactly: the adapters swap the
    recurrent state / decoder KV / encoder context out and back instead of
    recomputing (recompute would change float summation order)."""
    cfg = CFGS[family]
    params = dense_params[family]
    prompts = _prompts(cfg, 3, P_LEN, seed=4)
    ref, embeds = _ref(cfg, params, prompts, GEN)
    engine = ServingEngine(cfg, params, n_slots=4, max_len=P_LEN + GEN,
                           token_budget=8, max_ctx=7)
    reqs = _submit_all(engine, cfg, prompts, GEN, embeds)
    # advance until at least one request is decoding, then force a
    # preemption (slot layouts never hit memory pressure on their own)
    for _ in range(32):
        engine.step()
        if any(r.tokens for r in engine.running.values()):
            break
    engine._preempt_one({"preempted": 0})
    engine.run()
    assert engine.n_preemptions == 1
    assert any(r.n_preempted == 1 for r in reqs)
    for i, r in enumerate(reqs):
        assert r.status is Status.FINISHED
        assert r.tokens == ref[i].tolist(), f"{family} resumed req {i}"


def test_ssm_chunk_boundaries_are_invisible(dense_params):
    """An O(1)-state family has no block math: any token budget is legal
    (the quantum floor is waived) and odd chunk splits cannot change the
    stream."""
    cfg = CFGS["ssm"]
    params = dense_params["ssm"]
    prompts = _prompts(cfg, 2, P_LEN, seed=5)
    ref, _ = _ref(cfg, params, prompts, GEN)
    engine = ServingEngine(cfg, params, n_slots=4, max_len=P_LEN + GEN,
                           token_budget=5)        # < CHUNK_QUANTUM: ssm-only
    assert engine.token_budget == 5
    assert engine.chunk_quantum == 5              # widened to the budget
    reqs = _submit_all(engine, cfg, prompts, GEN)
    engine.run()
    for i, r in enumerate(reqs):
        assert r.tokens == ref[i].tolist()
    # the same sub-quantum budget is a construction-time error for a
    # paged-KV family, whose chunks must cover the block quantum
    dense_cfg = dataclasses.replace(configs.get_smoke("llama-paper"),
                                    n_layers=1, remat=False)
    with pytest.raises(ValueError, match="quantum|budget"):
        ServingEngine(dense_cfg, None, token_budget=5)


# ------------------------------------------------- family admission rules ---

def test_ssm_coerces_layout_and_rejects_embeds(dense_params):
    cfg = CFGS["ssm"]
    engine = ServingEngine(cfg, dense_params["ssm"], n_slots=2, max_len=32,
                           kv_layout="paged")     # nothing to page
    assert engine.kv_layout == "slot"
    with pytest.raises(ValueError, match="embeds"):
        engine.submit([1, 2, 3], SamplingParams(max_new_tokens=2),
                      embeds=np.zeros((4, cfg.d_model), np.float32))


def test_encdec_requires_embeds_and_bounds_ctx(dense_params):
    cfg = CFGS["encdec"]
    engine = ServingEngine(cfg, dense_params["encdec"], n_slots=2,
                           max_len=32, max_ctx=8)
    with pytest.raises(ValueError, match="embeds"):
        engine.submit([1, 2, 3], SamplingParams(max_new_tokens=2))
    with pytest.raises(ValueError, match="max_ctx"):
        engine.submit([1, 2, 3], SamplingParams(max_new_tokens=2),
                      embeds=np.zeros((9, cfg.d_model), np.float32))
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(cfg, dense_params["encdec"], n_slots=2, max_len=32,
                      kv_layout="paged")


def test_hybrid_requires_shared_attention(dense_params):
    cfg = dataclasses.replace(CFGS["hybrid"], attn_every=0)
    with pytest.raises(ValueError, match="attn_every"):
        ServingEngine(cfg, dense_params["hybrid"], n_slots=2, max_len=32)


# ------------------------------------- RecurrentStatePool lifecycle walk ---

def _tiny_pool(n_slots=4):
    init = lambda _cfg, n: [(jnp.zeros((n, 2, 3)),
                             jnp.full((n, 3), -1.0))]
    return RecurrentStatePool(None, n_slots, max_len=32, init_states=init)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 32 - 1))
def test_recurrent_pool_invariant_walk(seed):
    """Random alloc/release/save/restore/adopt/advance walk: the free list
    and the arenas never desynchronize, slot ids are stable, double frees
    raise, and a save/restore round-trip is bitwise."""
    import random
    rng = random.Random(seed)
    pool = _tiny_pool()
    held, saved = [], {}
    for step in range(30):
        op = rng.choice(["alloc", "release", "save", "restore", "adopt",
                         "advance"])
        if op == "alloc":
            slot = pool.alloc()
            if len(held) == pool.n_slots:
                assert slot is None
            else:
                assert slot is not None and slot not in held
                held.append(slot)
            assert pool.n_free == pool.n_slots - len(held)
        elif op == "release" and held:
            slot = held.pop(rng.randrange(len(held)))
            pool.release(slot)
            saved.pop(slot, None)
            with pytest.raises(DoubleFree):
                pool.release(slot)
        elif op == "save" and held:
            slot = rng.choice(held)
            saved[slot] = (pool.save_slot(slot),
                           [np.asarray(l[slot])
                            for l in jax.tree.leaves(pool.states)])
        elif op == "restore" and saved:
            slot = rng.choice(list(saved))
            blob, want = saved[slot]
            pool.restore_slot(slot, blob)
            got = [np.asarray(l[slot]) for l in jax.tree.leaves(pool.states)]
            for g, w in zip(got, want):
                assert np.array_equal(g, w)       # swap round-trip: bitwise
        elif op == "adopt":
            # a jitted step hands back mutated arenas; ownership moves but
            # the tree structure and shapes must be preserved
            before = jax.tree.structure(pool.states)
            pool.adopt(jax.tree.map(lambda a: a + 1.0, pool.states))
            assert jax.tree.structure(pool.states) == before
        elif op == "advance" and held:
            pool.advance_prefill(held, [rng.randrange(32) for _ in held])
            mask = np.zeros((pool.n_slots,), bool)
            mask[held] = True
            pos = np.asarray(pool.pos).copy()
            pool.advance_decode(mask)
            assert np.array_equal(np.asarray(pool.pos), pos + mask)
    assert sorted(held + pool._free) == list(range(pool.n_slots))


# -------------------------------------------------- mesh-native shardings ---

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        return None
    return jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


@needs8
def test_mesh_recurrent_state_shardings(mesh, dense_params):
    """On a 1x8 model-axis mesh the recurrent-state arenas must actually
    distribute: every matrix-memory leaf (ndim >= 4) carries a
    non-replicated NamedSharding (heads when they divide, else the
    never-contracted value dim), and contraction dims stay whole."""
    for fam in ("ssm", "hybrid"):
        engine = ServingEngine(CFGS[fam], dense_params[fam], n_slots=4,
                               max_len=32, mesh=mesh)
        pool = engine.pool if fam == "ssm" else engine.pool.state
        n_sharded = 0
        for leaf in jax.tree.leaves(pool.states):
            assert isinstance(leaf.sharding, NamedSharding), fam
            if leaf.ndim >= 4:
                assert not leaf.sharding.is_fully_replicated, \
                    f"{fam} leaf {leaf.shape} replicated on 1x8"
                n_sharded += 1
        assert n_sharded > 0, fam
        assert engine.stats()["placement"]["devices"] == 8


@needs8
def test_mesh_family_engine_token_identical(mesh, dense_params):
    """Mesh-native recurrent serving produces exactly the single-device
    streams (the state shardings never split a contraction)."""
    cfg = CFGS["ssm"]
    params = dense_params["ssm"]
    prompts = _prompts(cfg, 2, P_LEN, seed=6)
    ref, _ = _ref(cfg, params, prompts, GEN)
    engine = ServingEngine(cfg, params, n_slots=4, max_len=P_LEN + GEN,
                           token_budget=8, mesh=mesh)
    reqs = _submit_all(engine, cfg, prompts, GEN)
    engine.run()
    for i, r in enumerate(reqs):
        assert r.tokens == ref[i].tolist(), f"mesh ssm req {i}"
