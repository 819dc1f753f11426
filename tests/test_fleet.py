"""Multi-replica fleet serving tests.

The load-bearing property mirrors test_serving's: WHERE a request runs
never changes WHAT it generates.  A fleet of N replicas (any routing
policy, even with a mid-trace work-steal) must emit per-request token
streams identical to one engine holding the fleet's total KV.  Around
that: router scoring unit tests on fake replicas, fleet_trace
determinism, drain/re-admit, replica meshes, stats schema, and the
RouterTracer's shared-buffer observability.
"""
import dataclasses

import jax
import pytest

from repro import configs
from repro.launch.mesh import make_replica_meshes
from repro.models import get_model
from repro.serving import (QueueFull, ReplicaSet, Router, RouterTracer,
                           SamplingParams, ServingEngine, ServingTracer,
                           fleet_trace, replay)

CFG = dataclasses.replace(configs.get_smoke("llama-paper"),
                          name="fleet-test", n_layers=2, d_model=128,
                          n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
                          vocab=512, remat=False)

# small but real fleet workload: 8:16-style tenant mix, heavy tails,
# bursts — shared across the identity tests so compiles amortize
TRACE_KW = dict(n_requests=16, n_tenants=4, vocab=CFG.vocab, sys_len=16,
                rate_per_s=200.0, burst_mean=3.0, prompt_median=6,
                prompt_sigma=0.5, prompt_max=16, gen_median=5,
                gen_sigma=0.8, gen_max=12, seed=11)
ENGINE_KW = dict(kv_layout="paged", block_size=4, max_len=48,
                 prefix_caching=True, max_queue=64, token_budget=32)


@pytest.fixture(scope="module")
def dense_params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def single_streams(dense_params):
    """Reference: the same trace through ONE engine with the fleet's
    total KV (16 blocks x 2 replicas), cold caches."""
    eng = ServingEngine(CFG, dense_params, n_slots=4, n_blocks=32,
                        **ENGINE_KW)
    res = replay(eng, fleet_trace(**TRACE_KW), time_scale=0.001)
    assert res["rejected"] == 0
    return {r.request_id: list(r.tokens) for r in res["finished"]}


# --------------------------------------------------------------------------
# router scoring (fake replicas: the router only reads queue/pool/cache)
# --------------------------------------------------------------------------

class _FakeQueue(list):
    def __init__(self, n, max_size=8):
        super().__init__(range(n))
        self.max_size = max_size


class _FakePool:
    def __init__(self, n_slots):
        self.n_slots = n_slots


class _Fake:
    def __init__(self, *, slots=4, running=0, queued=0, max_queue=8,
                 cached=0):
        self.pool = _FakePool(slots)
        self.running = list(range(running))
        self.queue = _FakeQueue(queued, max_queue)
        self._cached = cached

    def prefix_match_length(self, prompt):
        return min(self._cached, len(prompt))


def test_router_validates_policy_and_replicas():
    with pytest.raises(ValueError, match="unknown routing policy"):
        Router([_Fake()], "random")
    with pytest.raises(ValueError, match="at least one replica"):
        Router([], "prefix")


def test_round_robin_cycles_and_skips_full():
    reps = [_Fake(), _Fake(queued=8, max_queue=8), _Fake()]
    r = Router(reps, "round_robin")
    picks = [r.route([1, 2, 3]).replica for _ in range(4)]
    assert picks == [0, 2, 0, 2]            # replica 1's queue is full
    assert r.n_decisions == 4
    assert r.decisions_by == {"round_robin": 4}


def test_least_loaded_prefers_empty_replica():
    reps = [_Fake(running=4, queued=2), _Fake(running=1), _Fake(running=2)]
    r = Router(reps, "least_loaded")
    d = r.route(list(range(8)))
    assert d.replica == 1 and d.picked_by == "load"
    assert d.loads == (1.5, 0.25, 0.5)


def test_prefix_score_wins_on_cached_prompt():
    reps = [_Fake(), _Fake(cached=16), _Fake()]
    r = Router(reps, "prefix")
    d = r.route(list(range(16)))
    assert d.replica == 1 and d.picked_by == "prefix"
    assert d.prefix_tokens == 16 and d.prefix_frac == 1.0


def test_prefix_score_load_counterweight():
    # a fully-cached prompt on a replica with a full batch QUEUED behind
    # its running batch must lose to an idle cache-cold replica:
    # 2.0 * 1.0 - 1.0 * 2.0 = 0.0 <= idle's 0.0, tie broken by load
    reps = [_Fake(), _Fake(cached=16, running=4, queued=4)]
    r = Router(reps, "prefix")
    d = r.route(list(range(16)))
    assert d.replica == 0 and d.picked_by == "load"


def test_prefix_score_session_affinity_breaks_ties():
    reps = [_Fake(), _Fake(), _Fake()]
    r = Router(reps, "prefix")
    first = r.route(list(range(8)), session=7).replica
    d = r.route(list(range(8)), session=7)
    assert d.replica == first and d.picked_by == "affinity"
    # a different session has no home yet: cold-cache tie goes to the
    # least-loaded, lowest-indexed replica
    assert r.route(list(range(8)), session=8).picked_by == "load"


def test_router_queue_full_when_no_candidates():
    reps = [_Fake(queued=2, max_queue=2), _Fake(queued=2, max_queue=2)]
    for policy in ("prefix", "round_robin", "least_loaded"):
        with pytest.raises(QueueFull):
            Router(reps, policy).route([1, 2])


def test_router_stats_and_reset():
    r = Router([_Fake(cached=4), _Fake()], "prefix")
    r.route(list(range(4)), session=1)
    st = r.stats()
    assert st["n_decisions"] == 1 and st["prefix_tokens_routed"] == 4
    assert st["decisions_by"] == {"prefix": 1} and st["sessions"] == 1
    r.reset_stats()
    st = r.stats()
    assert st["n_decisions"] == 0 and st["decisions_by"] == {}
    assert st["sessions"] == 1               # routing state persists


# --------------------------------------------------------------------------
# fleet_trace: deterministic, tenant-structured workload
# --------------------------------------------------------------------------

def test_fleet_trace_deterministic_and_tenant_shaped():
    a = fleet_trace(**TRACE_KW)
    b = fleet_trace(**TRACE_KW)
    assert [(t.arrival_s, t.prompt, t.max_new_tokens, t.session)
            for t in a] == \
           [(t.arrival_s, t.prompt, t.max_new_tokens, t.session)
            for t in b]
    c = fleet_trace(**{**TRACE_KW, "seed": TRACE_KW["seed"] + 1})
    assert [t.prompt for t in a] != [t.prompt for t in c]

    sys_len = TRACE_KW["sys_len"]
    sys_prompts = {}
    # arrivals are near-sorted (bursts carry tiny intra-burst jitter that
    # can overtake the next epoch at high rates; replay sorts regardless)
    assert all(t.arrival_s > 0 for t in a)
    for t in a:
        assert 0 <= t.session < TRACE_KW["n_tenants"]
        assert len(t.prompt) <= sys_len + TRACE_KW["prompt_max"]
        assert 1 <= t.max_new_tokens <= TRACE_KW["gen_max"]
        assert all(0 <= tok < CFG.vocab for tok in t.prompt)
        # every request of a tenant opens with the SAME system prompt —
        # the sharing opportunity prefix routing exploits
        head = tuple(t.prompt[:sys_len])
        assert sys_prompts.setdefault(t.session, head) == head
    assert len(sys_prompts) > 1              # multiple tenants actually hit


# --------------------------------------------------------------------------
# token identity: 1 engine vs N replicas, cold caches
# --------------------------------------------------------------------------

def _fleet_streams(params, *, routing, n_replicas=2, steal_threshold=4,
                   **overrides):
    rs = ReplicaSet(CFG, params, n_replicas=n_replicas, routing=routing,
                    steal_threshold=steal_threshold, n_slots=4,
                    n_blocks=32 // n_replicas, **ENGINE_KW, **overrides)
    res = replay(rs, fleet_trace(**TRACE_KW), time_scale=0.001)
    assert res["rejected"] == 0
    return rs, {r.request_id: list(r.tokens) for r in res["finished"]}


@pytest.mark.parametrize("routing", ["prefix", "round_robin"])
def test_fleet_token_identical_to_single_engine(routing, dense_params,
                                                single_streams):
    rs, streams = _fleet_streams(dense_params, routing=routing)
    assert set(streams) == set(single_streams)
    for rid, toks in single_streams.items():
        assert streams[rid] == toks, f"request {rid} diverged under {routing}"
    # both replicas actually served work (routing didn't degenerate)
    served = [e.stats()["n_finished"] for e in rs.replicas]
    assert all(n > 0 for n in served)


def test_fleet_token_identical_with_forced_steal(dense_params,
                                                 single_streams):
    # steal_threshold=1 + prefix affinity piling one tenant's burst onto
    # its home replica forces mid-trace work-stealing; migrated requests
    # must still generate the exact same tokens
    rs, streams = _fleet_streams(dense_params, routing="prefix",
                                 steal_threshold=1)
    assert rs.n_steals > 0
    assert streams == single_streams


# --------------------------------------------------------------------------
# rebalance mechanics
# --------------------------------------------------------------------------

def test_drain_readmits_stuck_preempted_request(dense_params):
    rs = ReplicaSet(CFG, dense_params, n_replicas=2, routing="least_loaded",
                    n_slots=1, n_blocks=8, kv_layout="paged", block_size=4,
                    max_len=32, prefix_caching=True, max_queue=8)
    # occupy replica 0's only slot...
    rs.replicas[0].submit(list(range(8)), SamplingParams(max_new_tokens=16))
    rs.replicas[0].step()
    assert rs.replicas[0].pool.n_free == 0
    # ...and park a once-preempted request at the head of its queue:
    # it cannot re-admit here until its victim's slot frees, but
    # replica 1 could run it right now
    stuck = rs.replicas[0].submit(list(range(8)),
                                  SamplingParams(max_new_tokens=4))
    stuck.n_preempted = 1                    # simulate a prior eviction
    moved = rs._rebalance()
    assert moved == 1 and rs.n_drains == 1 and rs.n_steals == 0
    assert stuck not in rs.replicas[0].queue
    assert stuck in rs.replicas[1].queue
    assert rs.home[stuck.request_id] == 1


def test_replica_set_validates_shapes(dense_params):
    with pytest.raises(ValueError, match="n_replicas"):
        ReplicaSet(CFG, dense_params, n_replicas=0)
    with pytest.raises(ValueError, match="meshes"):
        ReplicaSet(CFG, dense_params, n_replicas=2, meshes=[None],
                   n_slots=1, max_len=16)


# --------------------------------------------------------------------------
# replica meshes
# --------------------------------------------------------------------------

def test_make_replica_meshes_default_and_bounds():
    n = len(jax.devices())
    assert make_replica_meshes(None, n + 1) == [None] * (n + 1)
    assert make_replica_meshes(None, 1) == [None]
    with pytest.raises(ValueError, match="n_replicas"):
        make_replica_meshes(None, 0)
    with pytest.raises(ValueError, match="devices"):
        make_replica_meshes("1x1", len(jax.devices()) + 1)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices for disjoint slices")
def test_make_replica_meshes_disjoint_slices():
    for spec in ("1x1", None):
        meshes = make_replica_meshes(spec, 2)
        d0 = set(meshes[0].devices.flat)
        d1 = set(meshes[1].devices.flat)
        assert d0 and d1 and not (d0 & d1)


# --------------------------------------------------------------------------
# stats schema + observability
# --------------------------------------------------------------------------

def test_fleet_stats_schema_and_reset(dense_params):
    rs, _ = _fleet_streams(dense_params, routing="prefix")
    st = rs.stats()
    assert st["n_replicas"] == 2 and st["routing"] == "prefix"
    assert len(st["busy_s"]) == 2 and len(st["replicas"]) == 2
    assert st["critical_path_s"] == \
        pytest.approx(max(st["busy_s"]) + st["router_busy_s"])
    assert st["prefix_cache"]["lookups"] > 0
    assert st["router"]["n_decisions"] == TRACE_KW["n_requests"]
    assert sum(p["n_finished"] for p in st["replicas"]) \
        == TRACE_KW["n_requests"]
    rs.reset_stats()
    st = rs.stats()
    assert st["busy_s"] == [0.0, 0.0] and st["router"]["n_decisions"] == 0
    assert st["n_steals"] == 0 and st["n_drains"] == 0


def test_router_tracer_shares_buffer_with_replica_tracers(dense_params):
    t0 = ServingTracer(name="r0")
    t1 = ServingTracer(buffer=t0.buffer, registry=t0.registry, name="r1")
    rt = RouterTracer(buffer=t0.buffer, registry=t0.registry)
    rs = ReplicaSet(CFG, dense_params, n_replicas=2, routing="prefix",
                    tracers=[t0, t1], router_tracer=rt, n_slots=4,
                    n_blocks=16, **ENGINE_KW)
    replay(rs, fleet_trace(**{**TRACE_KW, "n_requests": 8}),
           time_scale=0.001)
    events = t0.buffer.events
    routes = [e for e in events if e.get("name") == "route"]
    assert len(routes) == 8
    assert {e["args"]["replica"] for e in routes} <= {0, 1}
    # one buffer, three processes: two replicas + the router
    names = {e["args"]["name"] for e in events
             if e.get("name") == "process_name"}
    assert {"engine r0", "engine r1", "fleet router"} <= names
    text = t0.registry.prometheus_text()
    assert "fleet_routing_decisions_total" in text
    assert "fleet_queue_imbalance" in text
