"""Continuous-batching serving engine (preallocated KV pools, interleaved
prefill/decode scheduling, per-request sampling + streaming callbacks).

  engine = ServingEngine(cfg, params, n_slots=8, max_len=256)
  req = engine.submit(prompt_tokens, SamplingParams(max_new_tokens=16))
  engine.run()            # or engine.step() under an external loop
  req.tokens              # generated ids; req.metrics has ttft/e2e/...

``kv_layout="slot"`` reserves a contiguous max_len KV region per request;
``kv_layout="paged"`` allocates block_size-token blocks on demand with
prefix sharing and preempt-to-queue under memory pressure (serving/paged/).
``token_budget=`` bounds the prefill tokens any step may spend: prompts
larger than the budget advance chunk-by-chunk across steps beside the
decode batch, so long prompts never stall everyone else's tokens
(``max_prefill_per_step`` is the deprecated request-count spelling).
``mesh=`` makes the engine tensor-parallel through the serving placement
layer (serving/placement.py) — token-identical to the single-device path.

Dense params and SparseWeight compressed params (the paper's 8:16 +
structured-outlier deployment) are served by the same engine.

``draft=SpeculativeConfig(...)`` turns on draft-verify speculative
decoding (serving/speculative.py): a cheap proposer — the 8:16-compressed
model, any second parameter set, or an n-gram prompt-lookup — drafts k
tokens per decoding request per step, and the target scores all k+1
positions in ONE fused verify call through the same step pipeline.
Greedy speculative streams are token-identical to non-speculative ones.

``tracer=ServingTracer()`` turns on the observability substrate
(serving/observe.py): Perfetto trace spans for every request lifecycle and
engine phase, and a Prometheus-text counter registry.  The default
NULL_TRACER is a no-op; the engine's ``serve.*`` phase spans are profiler
annotations either way, recorded only while a ``jax.profiler`` trace runs.
"""

from .cache_pool import (CachePoolError, CapacityError, DoubleFree,
                         KVCachePool, SlotKVPool, SlotPoolView)
from .engine import KV_LAYOUTS, ServingEngine, SUPPORTED_FAMILIES
from .families import (EncDecAdapter, FamilyAdapter, HybridAdapter,
                       RecurrentAdapter, TransformerAdapter, build_adapter)
from .fleet import ROUTING_POLICIES, ReplicaSet, RouteDecision, Router
from .observe import (NULL_ROUTER_TRACER, NULL_TRACER, NullRouterTracer,
                      NullTracer, RouterTracer, ServingTracer)
from .paged import OutOfBlocks, PagedKVPool, PagedPoolView
from .placement import ServingPlacement
from .request import Request, SamplingParams, Status
from .state_pool import (EncDecPoolView, EncoderContextPool, HybridPoolView,
                         RecurrentStatePool, RecurrentStateView)
from .scheduler import (CHUNK_QUANTUM, PREEMPT_DECODE_PRESSURE,
                        PREEMPT_PREFILL_PRESSURE, QueueFull, RequestQueue,
                        plan_chunks, resolve_token_budget,
                        spec_verify_reserve, validate_token_budget)
from .speculative import (NGramProposer, SpeculativeConfig, Speculator,
                          verify_bucket)
from .trace import (TraceRequest, fleet_trace, load_trace, long_prompt_trace,
                    poisson_trace, replay, save_trace)
