"""Paged KV-cache subsystem tests.

Load-bearing properties:
  1. The paged engine is token-identical to the slot engine (dense AND
     8:16+outlier compressed weights) — paging is a memory layout, never
     a numerics change.
  2. Prefix-cache hits, copy-on-write, and preempt-to-queue never change
     a request's token stream either.
  3. The Pallas paged-attention kernel (interpret mode here) matches the
     jnp gather reference, which matches contiguous decode attention.
  4. Block accounting (refcounts, double free, exhaustion, LRU eviction)
     raises real exceptions and never leaks or aliases blocks.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import SparsifyConfig
from repro.models import get_model
from repro.models.layers import decode_attention
from repro.serving import SamplingParams, ServingEngine, Status
from repro.serving.paged import (BlockPool, BlockPoolError, BlockTable,
                                 OutOfBlocks, PagedKVPool, PrefixCache,
                                 blocks_needed, paged_attention_pallas,
                                 paged_attention_ref)

CFG = dataclasses.replace(configs.get_smoke("llama-paper"),
                          name="paged-test", n_layers=2, d_model=128,
                          n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
                          vocab=512, remat=False)
GEN = 6
BS = 8                                     # block size for engine tests


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


def _run(params, prompts, gen, **kw):
    engine = ServingEngine(CFG, params, **kw)
    reqs = [engine.submit(p, SamplingParams(max_new_tokens=gen))
            for p in prompts]
    engine.run()
    return engine, reqs


def _solo(params, prompt, gen):
    _, (r,) = _run(params, [prompt], gen, n_slots=1, max_len=64)
    return r.tokens


# --------------------------------------------------------------------------
# allocator / table / prefix-cache units
# --------------------------------------------------------------------------

def test_block_pool_refcounts_and_exhaustion():
    pool = BlockPool(CFG, n_blocks=3, block_size=4)
    a, b, c = pool.alloc(), pool.alloc(), pool.alloc()
    assert pool.n_free == 0 and sorted((a, b, c)) == [0, 1, 2]
    with pytest.raises(OutOfBlocks):
        pool.alloc()
    pool.incref(a)
    assert not pool.decref(a)              # shared: not yet freed
    assert pool.decref(a) and pool.n_free == 1
    with pytest.raises(BlockPoolError):    # double free
        pool.decref(a)
    with pytest.raises(BlockPoolError):    # incref of a free block
        pool.incref(a)
    pool.decref(b), pool.decref(c)
    assert pool.n_free == 3


def test_copy_on_write_preserves_content_and_refs():
    pool = BlockPool(CFG, n_blocks=2, block_size=4)
    src = pool.alloc()
    pool.k = pool.k.at[:, src].set(7.0)
    pool.incref(src)                       # two owners
    dst = pool.copy_on_write(src)
    assert dst != src
    assert pool.ref[src] == 1 and pool.ref[dst] == 1
    np.testing.assert_array_equal(np.asarray(pool.k[:, dst]),
                                  np.asarray(pool.k[:, src]))


def test_block_table_mapping():
    t = BlockTable(4, [9, 2, 5])
    assert t.capacity == 12 and t.n_blocks == 3
    assert t.physical_block(0) == 9 and t.physical_block(7) == 2
    assert t.slot(6) == 2 * 4 + 2 and t.slot(11) == 5 * 4 + 3
    assert blocks_needed(0, 4) == 0 and blocks_needed(9, 4) == 3


def test_prefix_cache_match_insert_evict():
    pool = BlockPool(CFG, n_blocks=3, block_size=4)
    cache = PrefixCache(pool)
    toks = list(range(11))                       # 2 full blocks + tail of 3
    blocks = [pool.alloc() for _ in range(3)]
    cache.insert(toks, blocks)
    assert len(cache) == 2                       # only full blocks cached
    assert pool.ref[blocks[0]] == 2 and pool.ref[blocks[2]] == 1

    m = cache.match(toks)
    assert m == blocks[:2]                       # chain hit, increfed for us
    assert pool.ref[blocks[0]] == 3
    assert cache.match(list(range(100, 111))) == []   # different prefix
    # chain property: same second block tokens under a different first
    # block must NOT match
    assert cache.match([99] * 4 + toks[4:]) == []

    for b in m:
        pool.decref(b)
    for b in blocks:                             # request releases its table
        pool.decref(b)
    assert cache.n_evictable == 2
    assert cache.evict_one() and pool.n_free == 2   # child evicted first
    assert cache.evict_one() and not cache.evict_one()
    assert len(cache) == 0


def test_prefix_cache_match_length_probe_is_side_effect_free():
    pool = BlockPool(CFG, n_blocks=4, block_size=4)
    cache = PrefixCache(pool)
    toks = list(range(11))                       # 2 full blocks + tail of 3
    blocks = [pool.alloc() for _ in range(3)]
    cache.insert(toks, blocks)
    order_after_insert = list(cache._entries)

    # exact multiples, partial tails, and the chain property
    assert cache.match_length(toks) == 8         # tail never matches
    assert cache.match_length(toks[:8]) == 8
    assert cache.match_length(toks[:7]) == 4     # partial second block
    assert cache.match_length(toks[:4]) == 4
    assert cache.match_length(toks[:3]) == 0
    assert cache.match_length(toks + [99] * 8) == 8
    assert cache.match_length([99] * 4 + toks[4:]) == 0
    assert cache.match_length([]) == 0

    # the router probes every replica per request: NO refcounts taken,
    # NO LRU touch, NO hit/lookup accounting — probes counted apart
    assert [int(pool.ref[b]) for b in blocks] == [2, 2, 1]
    assert list(cache._entries) == order_after_insert
    st = cache.stats()
    assert st["probes"] == 8
    assert st["lookups"] == 0 and st["hits"] == 0 and st["hit_tokens"] == 0
    m = cache.match(toks)                        # admission lookup DOES count
    assert cache.stats()["lookups"] == 1 and cache.stats()["hits"] == 1
    for b in m:
        pool.decref(b)


def test_pool_prefix_match_length_passthrough():
    pool = PagedKVPool(CFG, n_rows=4, max_len=32, block_size=4)
    p = list(range(10))
    assert pool.prefix_match_length(p) == 0      # cold cache
    row, _ = pool.admit(p)
    pool.register_prefix(row, p)
    assert pool.prefix_match_length(p) == 8      # 2 full blocks cached
    assert pool.prefix_match_length(p[:5]) == 4
    assert pool.prefix_match_length([7] + p) == 0


def test_pool_admit_shares_and_releases():
    pool = PagedKVPool(CFG, n_rows=4, max_len=32, block_size=4)
    p = list(range(10))                          # 3 blocks
    row, n_cached = pool.admit(p)
    assert n_cached == 0
    assert pool.tables[row].n_blocks == 3
    pool.register_prefix(row, p)
    row2, n_cached2 = pool.admit(p)
    assert n_cached2 == 8                        # 2 full blocks shared
    assert pool.tables[row2].blocks[:2] == pool.tables[row].blocks[:2]
    assert pool.tables[row2].blocks[2] != pool.tables[row].blocks[2]
    free_before = pool.blocks.n_free
    pool.release(row2)
    assert pool.blocks.n_free == free_before + 1  # shared blocks survive
    from repro.serving import DoubleFree
    with pytest.raises(DoubleFree):
        pool.release(row2)


# --------------------------------------------------------------------------
# paged attention numerics
# --------------------------------------------------------------------------

def _attn_case(seed=0, B=3, S=1, H=4, KV=2, hd=16, bs=8, n_blocks=10, nb=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    ka = jax.random.normal(ks[1], (n_blocks, bs, KV, hd), jnp.float32)
    va = jax.random.normal(ks[2], (n_blocks, bs, KV, hd), jnp.float32)
    bt = jnp.asarray(np.array([[3, 1, 7, 0], [2, 4, 5, 9], [8, 6, 0, 0]],
                              np.int32))
    # cursor = tokens visible before this step's S fresh ones; keep every
    # row's last visible position inside its real blocks
    cursor = jnp.asarray([27 - S, 12 - S, 9 - S], jnp.int32)
    return q, ka, va, bt, cursor


TRASH = 0


def _wide_case(S=1, H=4, KV=2, hd=16, bs=8, nb=37, seed=0):
    """Rows on the kernel's edges in tables wider than one walk step that
    its ``P`` does not divide: cursors 0, bs-1, bs, one either side of
    P*bs, a row whose last query sits at the table's last position, and
    two free rows (cursor 0, all-trash tables).  A row's table entries
    past its last written block are the trash block, as the pool leaves
    them.  Returns the case and each row's count of visible tokens."""
    from repro.serving.paged.paged_attention import kv_walk
    _, P = kv_walk(S, H, KV, hd, bs, nb, 4)
    assert nb % P and P * bs + 1 + S <= nb * bs
    cursor = np.array([0, bs - 1, bs, P * bs - 1, P * bs + 1,
                       nb * bs - S, 0, 0], np.int32)
    free = [6, 7]
    B = len(cursor)
    perm = np.random.default_rng(seed).permutation(
        np.arange(1, 1 + B * nb))
    bt = np.full((B, nb), TRASH, np.int32)
    for b in range(B):
        if b not in free:
            n = -(-(cursor[b] + S) // bs)
            bt[b, :n] = perm[b * nb:b * nb + n]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    shape = (1 + B * nb, bs, KV, hd)
    ka = jax.random.normal(ks[1], shape, jnp.float32)
    va = jax.random.normal(ks[2], shape, jnp.float32)
    return (q, ka, va, jnp.asarray(bt), jnp.asarray(cursor)), cursor + S


# the kernel and the reference take whole [L, ...] arenas and a layer
# index; every case sits at a non-zero layer between layers that hold NaN
# (int8 values: NaN scales), so reading any other layer shows
LAYER, N_LAYERS = 1, 3


def _at_layer(*arenas):
    """Each per-layer arena (or None) as layer ``LAYER`` of an
    ``N_LAYERS``-layer arena whose other layers are NaN (int8 values:
    zeros, whose NaN scales dequantize them to NaN)."""
    def one(a):
        if a is None:
            return None
        fill = jnp.zeros_like(a) if a.dtype == jnp.int8 \
            else jnp.full_like(a, jnp.nan)
        return jnp.stack([a if i == LAYER else fill
                          for i in range(N_LAYERS)])
    return tuple(one(a) for a in arenas)


def _quantize(a):
    """int8 values and per-position f32 scales of an f32 arena."""
    sc = jnp.max(jnp.abs(a), axis=-1) / 127.0
    return jnp.round(a / sc[..., None]).astype(jnp.int8), sc


def _case(which, S, H=4, KV=2):
    """(q, k, v, tables, cursor, k_scale, v_scale) of a named case."""
    if which == "narrow":
        return _attn_case(S=S, H=H, KV=KV) + (None, None)
    q, ka, va, bt, cursor = _wide_case(S=S, H=H, KV=KV)[0]
    if which == "wide":
        return q, ka, va, bt, cursor, None, None
    (ka, ks), (va, vs) = _quantize(ka), _quantize(va)
    return q, ka, va, bt, cursor, ks, vs


def test_paged_attention_ref_matches_contiguous():
    q, ka, va, bt, cursor = _attn_case()
    ref = paged_attention_ref(q, *_at_layer(ka, va), bt, cursor,
                              layer=LAYER)
    # contiguous view assembled by the same table; decode masks < len
    B, nb = bt.shape
    bs = ka.shape[1]
    kc = ka[bt].reshape(B, nb * bs, *ka.shape[2:])
    vc = va[bt].reshape(B, nb * bs, *va.shape[2:])
    ctg = decode_attention(q, kc, vc, cursor + 1)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ctg))


def test_paged_attention_ref_chunk_matches_masked_contiguous():
    """S>1 queries (a prefill chunk): query i of row b sees gathered
    positions <= cursor[b] + i — identical to length-masked attention
    over the contiguous view assembled by the same tables."""
    from repro.models.layers import attend_length_masked
    q, ka, va, bt, cursor = _attn_case(S=5)
    ref = paged_attention_ref(q, *_at_layer(ka, va), bt, cursor,
                              layer=LAYER)
    B, nb = bt.shape
    bs = ka.shape[1]
    kc = ka[bt].reshape(B, nb * bs, *ka.shape[2:])
    vc = va[bt].reshape(B, nb * bs, *va.shape[2:])
    ctg = attend_length_masked(q, kc, vc, cursor)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ctg))


@pytest.mark.parametrize("case", ["narrow", "wide", "wide-int8"])
@pytest.mark.parametrize("S", [1, 5])
@pytest.mark.parametrize("window", [None, 10])
def test_paged_attention_pallas_matches_ref(window, S, case):
    """The kernel against the gather reference: three-row tables within
    one walk step, and wide tables (``_wide_case``) with rows on every
    edge of the walk, f32 and int8 arenas; the window makes
    later rows start their walk past block 0.  Both read layer ``LAYER``
    of whole arenas whose other layers are NaN."""
    q, ka, va, bt, cursor, ks, vs = _case(case, S)
    ka, va = _at_layer(ka, va)
    ks, vs = _at_layer(ks, vs)
    ref = paged_attention_ref(q, ka, va, bt, cursor, layer=LAYER,
                              window=window, k_scale=ks, v_scale=vs)
    pal = paged_attention_pallas(q, ka, va, bt, cursor, layer=LAYER,
                                 window=window, interpret=True,
                                 k_scale=ks, v_scale=vs)
    assert np.isfinite(np.asarray(pal)).all()
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("case", ["narrow", "wide"])
@pytest.mark.parametrize("window", [None, 10])
@pytest.mark.parametrize("S,H,KV", [(8, 4, 4), (8, 8, 4), (4, 8, 4),
                                    (8, 8, 2)])
def test_paged_attention_pallas_query_tiled_matches_ref(S, H, KV, window,
                                                        case, monkeypatch):
    """Chunks longer than one query tile walk query tiles, each at its own
    position offset and over its own visible blocks, for MHA and GQA head
    groupings (1, 2 and 4 query heads per KV head); forced to small tiles
    here by shrinking the row budget (the chip's budget splits 512-token
    chunks)."""
    import importlib
    pa = importlib.import_module("repro.serving.paged.paged_attention")
    monkeypatch.setattr(pa, "_Q_ROWS", 8)
    q, ka, va, bt, cursor, _, _ = _case(case, S, H=H, KV=KV)
    assert pa._q_tile(S, H) < S
    ka, va = _at_layer(ka, va)
    ref = paged_attention_ref(q, ka, va, bt, cursor, layer=LAYER,
                              window=window)
    pal = paged_attention_pallas(q, ka, va, bt, cursor, layer=LAYER,
                                 window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("S,window", [(1, None), (5, None), (5, 10)])
def test_paged_attention_pallas_never_reads_past_visible(S, window):
    """Every table entry past a row's visible length points at a block
    no row can see, and every such block is NaN: the kernel must neither
    fetch nor weigh them (a masked probability of 0 times a NaN value is
    NaN), as one that walks the whole table does.  The reference runs on
    the same tables with those blocks finite."""
    (q, ka, va, bt, cursor), n_visible = _wide_case(S=S)
    bs = ka.shape[1]
    bt = np.asarray(bt).copy()
    n_blocks = -(-n_visible // bs)
    seen = np.zeros(ka.shape[0], bool)
    for b, n in enumerate(n_blocks):
        seen[bt[b, :n]] = True
    unseen = np.flatnonzero(~seen)
    for b, n in enumerate(n_blocks):
        bt[b, n:] = unseen[np.arange(bt.shape[1] - n) % len(unseen)]
    bt = jnp.asarray(bt)
    hidden = jnp.asarray(~seen)[:, None, None, None]
    ref = paged_attention_ref(q, *_at_layer(ka, va), bt, cursor,
                              layer=LAYER, window=window)
    pal = paged_attention_pallas(
        q, *_at_layer(jnp.where(hidden, jnp.nan, ka),
                      jnp.where(hidden, jnp.nan, va)),
        bt, cursor, layer=LAYER, window=window, interpret=True)
    assert np.isfinite(np.asarray(pal)).all()
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_query_tile_sizes():
    from repro.serving.paged.paged_attention import _q_tile
    assert _q_tile(1, 32) == 1 and _q_tile(64, 16) == 64
    assert _q_tile(512, 16) == 256 and _q_tile(512, 32) == 128
    assert _q_tile(384, 16) == 128 and _q_tile(4096, 8) == 512


@pytest.mark.parametrize("S", [1, 5])
def test_paged_attention_dispatch_matches_ref(S, monkeypatch):
    """The ``pallas`` backend of the dispatch entry point (the kernel the
    chip serves from, interpreted here) agrees with the reference, with
    and without an explicit backend."""
    from repro.serving.paged import paged_attention
    q, ka, va, bt, cursor = _attn_case(S=S, H=8, KV=2)
    ka, va = _at_layer(ka, va)
    ref = paged_attention(q, ka, va, bt, cursor, layer=LAYER,
                          backend="reference")
    monkeypatch.setenv("REPRO_PAGED_BACKEND", "pallas")
    for backend in ("pallas", None):
        pal = paged_attention(q, ka, va, bt, cursor, layer=LAYER,
                              backend=backend)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# engine: paged == slot, prefix sharing, CoW, preemption
# --------------------------------------------------------------------------

@pytest.mark.parametrize("which", ["dense", "sparse"])
def test_paged_engine_token_identical_to_slot(which, dense_params,
                                              sparse_params):
    params = dense_params if which == "dense" else sparse_params
    prompts = _prompts(4, 16)
    _, slot_reqs = _run(params, prompts, GEN, n_slots=4, max_len=32)
    _, paged_reqs = _run(params, prompts, GEN, n_slots=4, max_len=32,
                         kv_layout="paged", block_size=BS)
    for i, (s, p) in enumerate(zip(slot_reqs, paged_reqs)):
        assert p.status is Status.FINISHED
        assert p.tokens == s.tokens, f"request {i} diverged"


def test_prefix_cache_hits_are_token_identical(dense_params):
    """Requests sharing a system prompt: later ones hit the prefix cache
    (suffix-only prefill) yet produce exactly their solo tokens."""
    sys_prompt = _prompts(1, 3 * BS, seed=5)[0]       # 3 full blocks
    tails = _prompts(3, 6, seed=6)
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=64,
                           kv_layout="paged", block_size=BS)
    reqs = []
    for tail in tails:                    # sequential so the cache is warm
        reqs.append(engine.submit(sys_prompt + tail,
                                  SamplingParams(max_new_tokens=GEN)))
        engine.run()
    stats = engine.pool.prefix_cache.stats()
    assert stats["hit_tokens"] >= 2 * 3 * BS          # reqs 2,3 hit 3 blocks
    for tail, r in zip(tails, reqs):
        assert r.tokens == _solo(dense_params, sys_prompt + tail, GEN)


def test_fully_cached_prompt_copy_on_write(dense_params):
    """An identical prompt of exactly full blocks: the repeat admission
    matches every block, CoWs the last one to recompute its tail, and
    still emits identical tokens — the shared original stays intact."""
    prompt = _prompts(1, 3 * BS, seed=7)[0]
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=64,
                           kv_layout="paged", block_size=BS)
    r1 = engine.submit(prompt, SamplingParams(max_new_tokens=GEN))
    engine.run()
    r2 = engine.submit(prompt, SamplingParams(max_new_tokens=GEN))
    engine.run()
    r3 = engine.submit(prompt + _prompts(1, 4, seed=8)[0],
                       SamplingParams(max_new_tokens=GEN))
    engine.run()                         # r3 shares the SAME cached blocks
    assert r1.tokens == r2.tokens == _solo(dense_params, prompt, GEN)
    assert r3.tokens == _solo(dense_params, r3.prompt, GEN)


def test_preemption_resumes_identically(dense_params):
    """A starved arena forces preempt-to-queue mid-decode; every request
    still finishes with exactly its solo token stream."""
    prompts = _prompts(4, 16, seed=9)
    engine, reqs = _run(dense_params, prompts, 12, n_slots=4, max_len=40,
                        kv_layout="paged", block_size=BS, n_blocks=10,
                        prefix_caching=False)
    assert engine.n_preemptions > 0
    assert all(r.status is Status.FINISHED for r in reqs)
    assert any(r.n_preempted > 0 for r in reqs)
    for p, r in zip(prompts, reqs):
        assert r.tokens == _solo(dense_params, p, 12)


def test_block_exhaustion_defers_admission(dense_params):
    """More burst than blocks: admission stays block-aware (no OutOfBlocks
    escapes), deferred requests run as memory frees, order preserved."""
    prompts = _prompts(6, 16, seed=10)
    engine, reqs = _run(dense_params, prompts, GEN, n_slots=6, max_len=32,
                        kv_layout="paged", block_size=BS, n_blocks=9)
    assert all(r.status is Status.FINISHED for r in reqs)
    for p, r in zip(prompts, reqs):
        assert r.tokens == _solo(dense_params, p, GEN)


def test_paged_capacity_validation(dense_params):
    engine = ServingEngine(CFG, dense_params, n_slots=2, max_len=64,
                           kv_layout="paged", block_size=BS, n_blocks=4)
    # 4 blocks * 8 = 32 tokens is the real capacity, not max_len
    assert engine.pool.max_request_tokens == 32
    with pytest.raises(ValueError):
        engine.submit(_prompts(1, 30, seed=11)[0],
                      SamplingParams(max_new_tokens=8))


def test_full_capacity_request_admits_despite_lookahead(dense_params):
    """A request whose prompt+generation fills the whole arena is legal
    (submit bounds it by capacity); the lookahead margin must not defer
    it forever (regression: admission livelock in engine.run())."""
    engine = ServingEngine(CFG, dense_params, n_slots=1, max_len=32,
                           kv_layout="paged", block_size=BS)  # 4 blocks
    req = engine.submit(_prompts(1, 28, seed=14)[0],
                        SamplingParams(max_new_tokens=4))
    engine.run(max_steps=50)
    assert req.status is Status.FINISHED and len(req.tokens) == 4


def test_preempted_requests_exempt_from_queue_timeout():
    """Timeout eviction bounds the wait for FIRST service only: a request
    preempted back to the queue with generated tokens must not be dropped
    (that would silently discard completed work)."""
    from repro.serving import RequestQueue
    from repro.serving.request import Request
    q = RequestQueue(max_size=4, queue_timeout_s=5.0)
    fresh_stale = Request(0, [1, 2])
    fresh_stale.metrics.arrival = 0.0
    preempted = Request(1, [3, 4])
    preempted.metrics.arrival = 0.0
    preempted.tokens = [7]
    preempted.n_preempted = 1
    q.try_push(fresh_stale)
    q.push_front(preempted)
    evicted = q.evict_expired(now=100.0)
    assert evicted == [fresh_stale]
    assert q.pop() is preempted


def test_paged_moe_sliding_window_identical():
    """MoE + sliding-window + GQA (mixtral smoke) through the paged path:
    the windowed mask over gathered blocks matches the slot layout."""
    cfg = configs.get_smoke("mixtral-8x7b")
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    prompts = [t.tolist() for t in
               jax.random.randint(jax.random.PRNGKey(1), (3, 24), 0,
                                  cfg.vocab)]
    outs = []
    for layout in ("slot", "paged"):
        engine = ServingEngine(cfg, params, n_slots=3, max_len=48,
                               kv_layout=layout, block_size=BS)
        reqs = [engine.submit(p, SamplingParams(max_new_tokens=8))
                for p in prompts]
        engine.run()
        assert all(r.status is Status.FINISHED for r in reqs)
        outs.append([r.tokens for r in reqs])
    assert outs[0] == outs[1]


def test_mixed_arrivals_paged(dense_params):
    """Requests joining a running paged batch mid-decode match their solo
    runs (same property the slot engine guarantees)."""
    early = _prompts(2, 16, seed=12)
    late = _prompts(2, 11, seed=13)
    engine = ServingEngine(CFG, dense_params, n_slots=4, max_len=64,
                           kv_layout="paged", block_size=BS)
    reqs = [engine.submit(p, SamplingParams(max_new_tokens=12))
            for p in early]
    for _ in range(3):
        engine.step()
    reqs += [engine.submit(p, SamplingParams(max_new_tokens=4))
             for p in late]
    engine.run()
    assert [len(r.tokens) for r in reqs] == [12, 12, 4, 4]
    for r, prompt, gen in [(reqs[0], early[0], 12), (reqs[2], late[0], 4),
                           (reqs[3], late[1], 4)]:
        assert r.tokens == _solo(dense_params, prompt, gen)


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_step_programs_write_the_arena_in_place(kv_dtype):
    """The paged step programs carry the whole [L, ...] arenas through the
    layer loop and write each layer's fresh KV at its own index.
    Compiled, neither the decode program nor a chunk program holds a
    temporary as large as one arena, nor a copy, slice or update whose
    output is the arena or one layer of it: a layer loop that slices each
    layer out and stacks the results back copies the whole arena on
    every step.  A float32 model, because the CPU backend widens a bf16
    scatter's operand to float32 (a chip's compiler scatters bf16 in
    place; ``tests/test_tpu_compile.py`` checks that at full size)."""
    import re
    cfg = dataclasses.replace(CFG, dtype=jnp.float32)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, n_slots=4, max_len=64,
                           kv_layout="paged", block_size=BS, n_blocks=2048,
                           kv_dtype="int8" if kv_dtype == "int8" else "bf16")
    a, pool = engine.adapter, engine.pool
    arena_bytes = pool.k.size * pool.k.dtype.itemsize
    layer_shape = ",".join(map(str, pool.k.shape[1:]))
    whole = re.compile(r"= \S+\[(\d+,)?%s\]\S* (copy|dynamic-slice|"
                       r"dynamic-update-slice)\(" % layer_shape)
    lanes = jnp.asarray(pool.lane_tables([], 2))
    zeros = jnp.zeros((2,), jnp.int32)
    lowered = {
        "decode": a._decode_fn.lower(a.params, *a._arena_args(),
                                     pool.block_tables, pool.pos,
                                     jnp.zeros((4, 1), jnp.int32)),
        "chunk": a._step_fn.lower(a.params, *a._arena_args(), lanes, zeros,
                                  zeros, jnp.zeros((2, 16), jnp.int32))}
    for name, low in lowered.items():
        assert "kv_write" in low.as_text(debug_info=True), name
        compiled = low.compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < arena_bytes, (name, temp, arena_bytes)
        copies = [ln for ln in compiled.as_text().splitlines()
                  if whole.search(ln)]
        assert not copies, (name, copies[:3])
