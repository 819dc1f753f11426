"""Serving benchmark: continuous batching under Poisson arrivals,
dense vs 8:16(+16:256 outlier) compressed weights, slot vs paged KV.

The scenarios:

1. Poisson open-loop workload (exponential interarrival gaps) replayed
   through the ServingEngine for each (weights, kv_layout) combination;
   reports throughput (generated tok/s) plus p50/p99 of time-to-first-
   token, per-token latency, and end-to-end request latency.
2. Shared-system-prompt burst under an EQUAL KV-memory budget: every
   request is one long shared prefix plus a short unique tail.  The slot
   layout must reserve max_len per request, capping concurrency at
   budget/max_len; the paged layout allocates blocks on demand and
   stores the shared prefix KV once (prefix cache), so it admits more
   concurrent requests and skips most prefill work (lower TTFT).
3. Long-prompt chunked-prefill stress at an EQUAL KV budget: short
   decode-heavy requests are mid-stream when very long prompts land.
   One-shot prefill stalls every decoder for the whole prompt (one giant
   inter-token gap); with ``--token-budget`` the prompt advances in
   chunks beside the decode batch.  Reports the pooled inter-token
   latency p99 (the decode-tail stall) and prefill chunk counts for both
   modes.
4. Mixed-family co-hosting: an xLSTM (ssm) engine and a dense engine
   share one host and wall clock, each replaying its own Poisson trace;
   the summary's ``families`` breakdown reports per-family tok/s and
   ttft/itl percentiles over the shared window.
5. Speculative decoding: the 8:16(+outlier) compressed model drafts
   --spec-k tokens per request per step for its DENSE COUNTERPART — the
   densified realization of the same compressed weights
   (``densify_params``), so the pair agrees the way a trained model and
   its above-threshold compression do without needing trained weights —
   on the paged layout, vs the non-speculative baseline and the
   model-free n-gram proposer on the same trace.  Records acceptance
   rate, accepted tokens/step, tok/s speedup, and a token-identity
   cross-check of every greedy stream against the baseline.
6. Equal-HBM KV dtype: bf16 vs int8 (+per-position scales) arenas sized
   to the same byte budget.  int8 admits ~2*hd/(hd+4) more slots, so
   under an oversubscribing burst it runs more requests concurrently;
   greedy replays record the quantized arena's token agreement rate
   against the bf16 reference.
7. Multi-replica fleet: N replicas behind the prefix-aware router vs
   round-robin vs ONE replica-sized engine holding the fleet's total KV
   (equal total KV budget), on a bursty multi-tenant trace with
   heavy-tailed lengths.  Reports host and critical-path tok/s (max
   per-replica busy time — what disjoint mesh slices would see),
   per-replica stats, routing-decision counters, prefix hit rate per
   policy, and a 1-vs-N token-identity cross-check.  See
   ``fleet_scenario`` for the baseline framing.

Every run also lands in a machine-readable ``BENCH_serving.json``
(--out) so the perf trajectory is tracked across PRs, with a top-level
``summary`` block (aggregate tok/s, worst-case ITL percentiles, and a
one-line digest per scenario) for trajectory diffs that don't have to
walk the per-scenario trees.  Summaries record the engine placement
(device count, mesh shape) and per-device tok/s; ``--mesh 1x8`` runs the
mesh-native tensor-parallel engine so single- vs multi-device results
compare on the same schema.

CPU smoke:   python benchmarks/serving_bench.py --smoke
Full-ish:    python benchmarks/serving_bench.py --requests 64 --rate 4 \
                 --slots 16 --gen 32
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax

sys.path.insert(0, "src")

from repro import configs                                      # noqa: E402
from repro.core import SparsifyConfig                          # noqa: E402
from repro.models import get_model                             # noqa: E402
from repro.models.sparse_serving import (densify_params,       # noqa: E402
                                         sparsify_for_serving)
from repro.runtime.compile_cache import use_compile_cache     # noqa: E402
from repro.runtime.metrics import format_summary, summarize    # noqa: E402
from repro.serving import (QueueFull, ServingEngine,           # noqa: E402
                           SpeculativeConfig, TraceRequest,
                           long_prompt_trace, poisson_trace, replay)


def bench_cfg(args):
    cfg = configs.get_smoke(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(cfg, n_layers=2, d_model=128, d_ff=256,
                                  vocab=512, remat=False)
    return cfg


# one trace buffer + counter registry shared by every engine the bench
# builds (each engine gets its own ServingTracer / process-id pair), so a
# --trace-out run lands the whole dense/sparse x slot/paged grid in a
# single Perfetto file.  None when tracing is off: engines run NULL_TRACER.
_OBS = {"buffer": None, "registry": None}


def _make_tracer(args, name: str):
    if not getattr(args, "trace_out", None):
        return None
    from repro.serving import ServingTracer
    from repro.runtime.telemetry import MetricsRegistry, TraceBuffer
    if _OBS["buffer"] is None:
        _OBS["buffer"] = TraceBuffer()
        _OBS["registry"] = MetricsRegistry()
    return ServingTracer(buffer=_OBS["buffer"], registry=_OBS["registry"],
                         name=name)


def _build_engine(cfg, params, args, kv_layout, *, n_slots=None,
                  max_len=None, n_blocks=None, token_budget=None,
                  prefix_caching=True, trace_name="", draft=None,
                  kv_dtype=None):
    from repro.launch.mesh import make_serving_mesh
    return ServingEngine(
        cfg, params, n_slots=n_slots or args.slots,
        max_len=max_len or args.max_len, max_queue=args.max_queue,
        token_budget=token_budget or args.token_budget,
        max_prefill_per_step=args.max_prefill_per_step,
        kv_layout=kv_layout, kv_dtype=kv_dtype or args.kv_dtype,
        block_size=args.block_size, n_blocks=n_blocks,
        prefix_caching=prefix_caching, mesh=make_serving_mesh(args.mesh),
        draft=draft, tracer=_make_tracer(args, trace_name or kv_layout))


def _warm_and_replay(engine, trace, time_scale) -> dict:
    """Replay untimed (compiles every prefill/decode shape the trace
    hits), then replay timed.  The paged engine is warmed twice: the
    first pass fills the prefix cache, the second compiles the
    suffix-prefill shapes that cache hits route through — the timed pass
    then measures prefix-cache steady state."""
    warm_passes = 2 if engine.kv_layout == "paged" else 1
    for _ in range(warm_passes):
        for t in trace:
            while True:                # drain when the queue fills up
                try:
                    engine.submit(t.prompt, t.sampling())
                    break
                except QueueFull:
                    engine.step()
        engine.run()
    engine.finished.clear()
    engine.reset_stats()               # measure only the timed window

    res = replay(engine, trace, time_scale=time_scale)
    summary = summarize([r.metrics for r in res["finished"]], res["wall_s"])
    summary["rejected"] = res["rejected"]
    summary.update(engine.stats())
    # engine.stats() carries placement (device count + mesh shape); add the
    # per-device rate so single- vs multi-device runs compare directly
    summary["tok_per_s_per_device"] = (
        summary["tok_per_s"] / max(summary["placement"]["devices"], 1))
    return summary


def run_one(name: str, cfg, params, trace, args, kv_layout) -> dict:
    engine = _build_engine(cfg, params, args, kv_layout, trace_name=name)
    summary = _warm_and_replay(engine, trace, args.time_scale)
    print(format_summary(name, summary))
    if summary["rejected"]:
        print(f"{'':>10}{summary['rejected']} rejected by admission control")
    return summary


def shared_prefix_scenario(cfg, params, args) -> dict:
    """Long shared system prompt + unique tails, arriving as one burst,
    slot vs paged under the same KV-memory budget (in cache tokens)."""
    import numpy as np
    rng = np.random.default_rng(args.seed + 1)
    sys_prompt = rng.integers(0, cfg.vocab, size=args.sys_len).tolist()
    n = args.shared_requests
    trace = []
    for i in range(n):
        tail = rng.integers(0, cfg.vocab, size=args.tail_len).tolist()
        trace.append(TraceRequest(arrival_s=0.001 * i,
                                  prompt=sys_prompt + tail,
                                  max_new_tokens=args.gen, seed=i))
    max_len = args.sys_len + args.tail_len + args.gen
    budget_tokens = args.kv_budget_tokens or args.slots * args.max_len
    slot_slots = max(budget_tokens // max_len, 1)
    paged_blocks = budget_tokens // args.block_size
    paged_rows = min(n, args.slots * 4)

    out = {"kv_budget_tokens": budget_tokens, "n_requests": n,
           "sys_len": args.sys_len, "tail_len": args.tail_len,
           "gen": args.gen}
    for layout, kw in (("slot", dict(n_slots=slot_slots, max_len=max_len)),
                       ("paged", dict(n_slots=paged_rows, max_len=max_len,
                                      n_blocks=paged_blocks))):
        engine = _build_engine(cfg, params, args, layout,
                               trace_name=f"sys/{layout}", **kw)
        summary = _warm_and_replay(engine, trace, args.time_scale)
        print(format_summary(f"sys/{layout}", summary))
        out[layout] = summary

    s, p = out["slot"], out["paged"]
    hits = p.get("pool", {}).get("prefix_cache", {}).get("hit_tokens", 0)
    print(f"shared-prefix @ {budget_tokens}-token KV budget: "
          f"max concurrent slot={s['max_running']} vs paged={p['max_running']}; "
          f"prefix-cache hit tokens={hits}; "
          f"ttft p50 slot={s['ttft']['p50']*1e3:.0f}ms vs "
          f"paged={p['ttft']['p50']*1e3:.0f}ms")
    return out


def kv_dtype_scenario(cfg, params, args) -> dict:
    """Equal-HBM-budget KV dtype comparison: bf16 vs int8 arenas sized to
    the SAME byte budget.  An int8 token costs ``hd + 4`` bytes per KV
    head (values + one f32 scale) against bf16's ``2*hd``, so the same
    bytes admit ``2*hd/(hd+4)`` more slots (~1.88x at hd=64).  A burst of
    more requests than either engine can hold measures admitted
    concurrency directly; greedy replays of the same trace measure
    per-token agreement of the quantized arena against the bf16
    reference."""
    import numpy as np
    rng = np.random.default_rng(args.seed + 9)
    hd = cfg.head_dim
    bf16_slots = args.kv_dtype_slots
    int8_slots = (bf16_slots * 2 * hd) // (hd + 4)
    n = int8_slots + 4                 # oversubscribe both engines
    plen = max(args.prompt_min, 4)
    trace = [TraceRequest(arrival_s=0.0005 * i,
                          prompt=rng.integers(0, cfg.vocab,
                                              size=plen).tolist(),
                          max_new_tokens=args.gen, seed=i)
             for i in range(n)]

    out = {"head_dim": hd, "bf16_slots": bf16_slots,
           "int8_slots": int8_slots, "n_requests": n, "gen": args.gen}
    toks = {}
    for dtype, slots in (("bf16", bf16_slots), ("int8", int8_slots)):
        engine = _build_engine(cfg, params, args, "slot", n_slots=slots,
                               kv_dtype=dtype, trace_name=f"kv/{dtype}")
        for t in trace:                # warm: compile every shape
            while True:
                try:
                    engine.submit(t.prompt, t.sampling())
                    break
                except QueueFull:
                    engine.step()
        engine.run()
        engine.finished.clear()
        engine.reset_stats()
        res = replay(engine, trace, time_scale=args.time_scale)
        summary = summarize([r.metrics for r in res["finished"]],
                            res["wall_s"])
        summary["rejected"] = res["rejected"]
        summary.update(engine.stats())
        toks[dtype] = {r.request_id: list(r.tokens)
                       for r in res["finished"]}
        print(format_summary(f"kv/{dtype}", summary))
        out[dtype] = summary

    # greedy agreement: positionwise match rate of int8 streams vs the
    # bf16 reference streams for the same requests
    matched = total = 0
    for rid, ref in toks["bf16"].items():
        got = toks["int8"].get(rid, [])
        total += len(ref)
        matched += sum(a == b for a, b in zip(ref, got))
    b16, i8 = out["bf16"], out["int8"]
    out["greedy_agreement"] = matched / total if total else 1.0
    out["concurrency_ratio"] = (i8["max_running"]
                                / max(b16["max_running"], 1))
    out["bf16_arena_bytes"] = b16["pool"]["arena_bytes"]
    out["int8_arena_bytes"] = i8["pool"]["arena_bytes"]
    print(f"kv-dtype @ equal HBM: int8 admits {i8['max_running']} vs "
          f"bf16 {b16['max_running']} concurrent "
          f"({out['concurrency_ratio']:.2f}x) at "
          f"{out['int8_arena_bytes']}/{out['bf16_arena_bytes']} arena "
          f"bytes; greedy agreement {out['greedy_agreement']:.3f}")
    return out


def long_prompt_scenario(cfg, params, args) -> dict:
    """Short decode-heavy requests mid-stream when long prompts land:
    one-shot prefill vs token-budget chunked prefill at an EQUAL KV
    budget (same arena, rows, and requests; only the step policy moves).
    The metric that matters is the pooled inter-token-latency p99 — the
    worst stall a decoding request observes."""
    max_len = args.long_len + args.gen
    trace = long_prompt_trace(
        n_short=args.long_short_requests, short_len=args.tail_len,
        gen_short=args.gen * 2, n_long=args.long_requests,
        long_len=args.long_len, gen_long=args.gen,
        vocab=cfg.vocab, seed=args.seed + 2)
    budget = args.token_budget or max(args.long_len // 4, 32)
    out = {"token_budget": budget, "long_len": args.long_len,
           "n_short": args.long_short_requests,
           "n_long": args.long_requests}
    # one-shot = a budget no prompt exceeds: the whole prompt lands in one
    # chunk, reproducing the pre-chunking schedule under identical memory.
    # Prefix caching is off so the measured pass repeats the warmed-up
    # prefill work instead of hitting KV the warm passes left behind —
    # the scenario measures prefill *scheduling*, not caching.
    for mode, tb in (("oneshot", 2 * max_len), ("chunked", budget)):
        engine = _build_engine(cfg, params, args, "paged",
                               n_slots=args.slots, max_len=max_len,
                               n_blocks=2 * max_len // args.block_size,
                               token_budget=tb, prefix_caching=False,
                               trace_name=f"long/{mode}")
        summary = _warm_and_replay(engine, trace, args.time_scale)
        print(format_summary(f"long/{mode}", summary))
        out[mode] = summary
    o, c = out["oneshot"], out["chunked"]
    print(f"long-prompt @ budget {budget} tok/step: itl p99 "
          f"oneshot={o['itl']['p99']*1e3:.1f}ms vs "
          f"chunked={c['itl']['p99']*1e3:.1f}ms; chunks max "
          f"{o['prefill_chunks']['max']} vs {c['prefill_chunks']['max']}")
    return out


def mixed_family_scenario(args) -> dict:
    """Co-hosted mixed-family serving: an xLSTM (ssm) engine and a dense
    transformer engine share one host and one wall clock, each replaying
    its own Poisson trace — O(1)-state recurrent serving and KV-pool
    serving contending for the same cores.  The pooled summary's
    ``families`` breakdown (runtime/metrics.py) reports each family's
    tok/s and latency tails over the SHARED window, which is the number
    that matters when deciding whether families can be co-scheduled or
    need separate hosts."""
    pairs = []
    for arch in ("xlstm-350m", args.arch):
        cfg = configs.get_smoke(arch)
        if args.smoke:
            cfg = dataclasses.replace(cfg, n_layers=2, remat=False)
        zoo = get_model(cfg)
        params = zoo.init(jax.random.PRNGKey(args.seed))
        trace = poisson_trace(
            n_requests=max(args.requests // 2, 2), rate_per_s=args.rate,
            vocab=cfg.vocab, prompt_len=(args.prompt_min, args.prompt_max),
            max_new_tokens=args.gen, seed=args.seed + len(pairs))
        engine = _build_engine(cfg, params, args, "slot",
                               trace_name=f"mixed/{cfg.family}")
        pairs.append((cfg.family, engine, trace))

    for _, engine, trace in pairs:              # warm: compile every shape
        for t in trace:
            engine.submit(t.prompt, t.sampling())
        engine.run()
        engine.finished.clear()
        engine.reset_stats()

    pending = sorted(((t.arrival_s, j, t, engine)
                      for _, engine, trace in pairs
                      for j, t in enumerate(trace)),
                     key=lambda e: e[0])
    t0 = time.monotonic()
    rejected, i = 0, 0
    while i < len(pending) or any(e.has_work for _, e, _ in pairs):
        now = time.monotonic() - t0
        while (i < len(pending)
               and pending[i][0] * args.time_scale <= now):
            _, _, tr, engine = pending[i]
            i += 1
            try:
                engine.submit(tr.prompt, tr.sampling())
            except QueueFull:
                rejected += 1
        stepped = False
        for _, engine, _ in pairs:
            if engine.has_work:
                engine.step()
                stepped = True
        if not stepped and i < len(pending):
            next_due = pending[i][0] * args.time_scale
            time.sleep(min(max(next_due - (time.monotonic() - t0), 0.0),
                           0.05))
    wall_s = time.monotonic() - t0

    metrics = [r.metrics for _, engine, _ in pairs for r in engine.finished]
    summary = summarize(metrics, wall_s)
    summary["rejected"] = rejected
    print(format_summary("mixed", summary))
    for fam, sub in summary.get("families", {}).items():
        print(f"{'':>10}{fam}: {sub['n_requests']} req, "
              f"{sub['tok_per_s']:.1f} tok/s, "
              f"ttft p50 {sub['ttft']['p50']*1e3:.0f}ms, "
              f"itl p99 {sub['itl']['p99']*1e3:.1f}ms")
    return summary


def speculative_scenario(cfg, args) -> dict:
    """The 8:16 model drafts for its dense counterpart.

    The pair is constructed so the agreement the paper measures on trained
    models (the compressed model crosses the Performance Threshold, so its
    tokens track the dense parent's) holds deterministically at smoke
    scale: the draft is the 8:16+outlier compression of a fresh init and
    the TARGET is ``densify_params`` of those same containers — the dense
    realization of the compressed weights, served through the dense matmul
    path while the draft runs the sparse kernels.  Three engines replay
    the same greedy trace on the paged layout: no draft (baseline), the
    sparse draft, and the model-free n-gram proposer.  Every stream must
    be token-identical to the baseline (greedy speculative decoding is
    exact); the sparse draft should accept nearly everything.
    """
    scfg = SparsifyConfig(weight_pattern=args.weight_pattern,
                          outlier_pattern=args.outlier_pattern,
                          scorer="magnitude", use_smoothquant=False)
    sparse, _ = sparsify_for_serving(
        get_model(cfg).init(jax.random.PRNGKey(args.seed)), scfg)
    target = densify_params(sparse)
    trace = poisson_trace(n_requests=args.requests, rate_per_s=args.rate,
                          vocab=cfg.vocab,
                          prompt_len=(args.prompt_min, args.prompt_max),
                          max_new_tokens=args.gen, seed=args.seed + 4)
    variants = (
        ("baseline", None),
        ("sparse_draft", SpeculativeConfig(k=args.spec_k, method="model",
                                           params=sparse, cfg=cfg)),
        ("ngram_draft", SpeculativeConfig(k=args.spec_k, method="ngram")),
    )
    out = {"spec_k": args.spec_k, "layout": "paged"}
    base_streams = None
    for name, draft in variants:
        engine = _build_engine(cfg, target, args, "paged", draft=draft,
                               trace_name=f"spec/{name}")
        summary = _warm_and_replay(engine, trace, args.time_scale)
        # greedy speculative streams must match the baseline exactly; the
        # i-th submitted request of the timed window is the one with the
        # i-th-smallest request id, in every engine
        streams = [r.tokens for r in
                   sorted(engine.finished, key=lambda r: r.request_id)]
        if base_streams is None:
            base_streams = streams
        else:
            summary["token_identical"] = streams == base_streams
        out[name] = summary
        line = format_summary(f"spec/{name}", summary)
        sp = summary.get("speculative")
        if sp:
            line += (f" | {sp['accepted_per_step']:.2f} acc tok/step "
                     f"(k={sp['k']})")
        print(line)
    base_tps = out["baseline"]["tok_per_s"]
    for name in ("sparse_draft", "ngram_draft"):
        s = out[name]
        s["speedup_vs_baseline"] = (s["tok_per_s"] / base_tps
                                    if base_tps > 0 else float("nan"))
    sd = out["sparse_draft"]
    print(f"speculative @ k={args.spec_k}: sparse-draft acceptance "
          f"{sd['speculative']['acceptance_rate']:.2f}, "
          f"{sd['speculative']['accepted_per_step']:.2f} accepted tok/step, "
          f"{sd['speedup_vs_baseline']:.2f}x tok/s vs baseline, "
          f"token_identical={sd['token_identical']}")
    return out


def prefill_curve_scenario(cfg, params, args) -> dict:
    """SLOW scenario (opt-in via --prefill-curve): very-long-prompt
    prefill time vs prompt length, chunked through the RETIRED
    gather-based path vs the in-place attend-over-pool path.

    The gathered baseline reconstructs PR 4's ``forward_with_prefix``
    schedule locally: every C-token chunk ships a gathered
    [L, 1, cursor, KV, hd] prefix copy into the step, so prefilling P
    tokens moves O(P^2/C) prefix bytes (and retraces once per cursor).
    The in-place path is ``transformer.unified_step`` over a slot view:
    the arena rides donated and the cursor is data, so per-chunk bytes
    are constant.  Each point records both wall time (same chunks, same
    prompt, 1 row, warm — compile excluded) and the ACCEPTANCE metric,
    ``step_bytes``: compiled bytes-accessed of the first vs last chunk —
    gathered grows with the cursor, in-place stays flat.  Wall times on a
    CPU smoke model are flop-bound (the masked in-place attention still
    computes over the whole arena row), so the bytes curve, not the
    milliseconds, is where the asymptote shows at small scale; on real
    HBM-bound serving shapes the bytes ARE the milliseconds.
    """
    import numpy as np
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    from repro.models.layers import linear, rms_norm
    from repro.serving import SlotPoolView

    C = args.curve_chunk
    lengths = [int(x) for x in args.curve_lens.split(",")]
    if any(P % C or P < C for P in lengths):
        raise ValueError(f"--curve-lens {lengths} must be multiples of "
                         f"--curve-chunk {C}")
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    rng = np.random.default_rng(args.seed + 3)

    def gathered_chunk(params, tokens, pk, pv):
        # the retired gather-based chunk primitive, kept ONLY as this
        # benchmark's baseline
        B, S = tokens.shape
        P = pk.shape[2]
        x = jnp.take(params["embed"], tokens, axis=0)
        positions = jnp.broadcast_to(P + jnp.arange(S)[None], (B, S))

        def body(h, xs):
            lp, pkl, pvl = xs
            h, kv = tfm.block_forward(lp, h, positions, cfg,
                                      prior_kv=(pkl, pvl))
            return h, kv
        x, (k, v) = jax.lax.scan(body, x, (params["layers"], pk, pv))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        return linear(head, x), (k, v)

    gathered_fn = jax.jit(gathered_chunk)
    inplace_fn = jax.jit(
        lambda p, k, v, cur, t: tfm.unified_step(
            p, SlotPoolView(k, v, None, cur, jnp.full((1,), C, jnp.int32)),
            {"tokens": t}, cfg),
        donate_argnums=(1, 2))

    def run_inplace(toks, P):
        k = jnp.zeros((L, 1, P, KV, hd), cfg.dtype)
        v = jnp.zeros((L, 1, P, KV, hd), cfg.dtype)
        for c in range(0, P, C):
            cur = jnp.asarray([c], jnp.int32)
            logits, (k, v) = inplace_fn(params, k, v, cur, toks[:, c:c + C])
        return logits

    def run_gathered(toks, P):
        pk = jnp.zeros((L, 1, 0, KV, hd), cfg.dtype)
        pv = jnp.zeros((L, 1, 0, KV, hd), cfg.dtype)
        for c in range(0, P, C):
            logits, (k, v) = gathered_fn(params, toks[:, c:c + C], pk, pv)
            pk = jnp.concatenate([pk, k], axis=2)
            pv = jnp.concatenate([pv, v], axis=2)
        return logits

    from repro.launch.hlo_analysis import cost_summary

    def step_bytes(P):
        """Compiled bytes-accessed of the FIRST vs LAST chunk step — the
        acceptance metric: the gathered step's bytes grow with the cursor
        (its prefix operand is [L, 1, cursor, KV, hd]); the in-place
        step's do not (the cursor is data, the arena operand is fixed)."""
        toks_c = jnp.zeros((1, C), jnp.int32)
        last = max(P - C, 0)
        out = {}
        for name, cur in (("first", 0), ("last", last)):
            pk = jnp.zeros((L, 1, cur, KV, hd), cfg.dtype)
            g = gathered_fn.lower(params, toks_c, pk, pk).compile()
            k = jnp.zeros((L, 1, P, KV, hd), cfg.dtype)
            v = jnp.zeros((L, 1, P, KV, hd), cfg.dtype)
            i = inplace_fn.lower(params, k, v, jnp.asarray([cur], jnp.int32),
                                 toks_c).compile()
            out[f"gathered_{name}"] = cost_summary(g)["bytes_accessed"]
            out[f"in_place_{name}"] = cost_summary(i)["bytes_accessed"]
        return out

    curve = []
    for P in lengths:
        toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(1, P)), jnp.int32)
        point = {"prompt_len": P, "chunk": C}
        for name, fn in (("in_place", run_inplace), ("gathered", run_gathered)):
            fn(toks, P).block_until_ready()          # warm: compile excluded
            t0 = time.perf_counter()
            for _ in range(args.curve_reps):
                fn(toks, P).block_until_ready()
            point[f"{name}_s"] = (time.perf_counter() - t0) / args.curve_reps
        point["speedup"] = point["gathered_s"] / max(point["in_place_s"], 1e-12)
        point["step_bytes"] = sb = step_bytes(P)
        g_growth = sb["gathered_last"] / max(sb["gathered_first"], 1.0)
        i_growth = sb["in_place_last"] / max(sb["in_place_first"], 1.0)
        print(f"prefill-curve P={P:5d} chunk={C}: in-place "
              f"{point['in_place_s']*1e3:8.1f}ms vs gathered "
              f"{point['gathered_s']*1e3:8.1f}ms ({point['speedup']:.2f}x); "
              f"step-bytes first->last chunk: gathered {g_growth:.2f}x vs "
              f"in-place {i_growth:.2f}x")
        curve.append(point)
    return {"chunk": C, "points": curve}


def _make_router_tracer(args, name: str):
    if not getattr(args, "trace_out", None):
        return None
    from repro.runtime.telemetry import MetricsRegistry, TraceBuffer
    from repro.serving import RouterTracer
    if _OBS["buffer"] is None:
        _OBS["buffer"] = TraceBuffer()
        _OBS["registry"] = MetricsRegistry()
    return RouterTracer(buffer=_OBS["buffer"], registry=_OBS["registry"],
                        name=name)


def _adopt_compiled(src, dst) -> None:
    """Alias ``src``'s jitted step functions into every replica of
    ``dst`` (identically-configured ReplicaSets trace identical shapes,
    and the functions close over constants only) so the second fleet
    reuses the first's compile cache instead of re-paying every (B, S)
    variant."""
    a0 = src.replicas[0].adapter
    for e in dst.replicas:
        for fn in ("_step_fn", "_decode_fn", "_encode_fn"):
            if hasattr(a0, fn):
                setattr(e.adapter, fn, getattr(a0, fn))
        e._step_fn = e.adapter._step_fn
        e._decode_fn = e.adapter._decode_fn


def _fleet_warm_and_replay(target, trace, time_scale, *, reps=2):
    """Warm with two full replays (arrival-paced, so warm passes compile
    the same chunk shapes the measured pass hits), then measure
    ``reps`` replays and keep the best critical path — a straggler jit
    variant that only a particular arrival interleaving reaches lands in
    the first measured pass, not the reported one.  Returns (summary,
    COLD token streams by request id): streams are captured from the
    first (cold-cache) pass, where 1-vs-N identity is exact — warmed
    runs reuse prefix-cache KV whose float rounding depends on how the
    warming pass happened to chunk it, which can flip a greedy near-tie
    when comparing DIFFERENTLY-SHAPED targets (within one target the
    cache holds exactly the KV that engine wrote, so its streams stay
    self-consistent)."""
    cold = None
    for i in range(2):
        res = replay(target, trace, time_scale=time_scale)
        if i == 0:
            cold = {r.request_id: list(r.tokens)
                    for r in res["finished"]}
        target.clear_finished()
    best = None
    for _ in range(reps):
        target.reset_stats()
        res = replay(target, trace, time_scale=time_scale)
        done = list(res["finished"])
        gen_tokens = sum(len(r.tokens) for r in done)
        metrics = [r.metrics for r in done]
        st = target.stats()
        crit = st.get("critical_path_s") or res["wall_s"]
        if best is None or crit < best[-1]:
            best = (res, gen_tokens, metrics, st, crit)
        target.clear_finished()
    res, gen, metrics, st, crit = best
    streams = cold
    summary = summarize(metrics, res["wall_s"])
    summary["rejected"] = res["rejected"]
    summary.update(st)
    summary["critical_path_s"] = crit
    summary["tok_per_s_critical_path"] = (gen / crit if crit > 0
                                          else float("nan"))
    return summary, streams


def fleet_scenario(cfg, params, args) -> dict:
    """Multi-replica fleet: N engine replicas behind a prefix-aware
    router, vs round-robin routing, vs a single replica-sized engine.

    Workload: heavy-tailed lognormal prompt/output lengths, bursty
    Poisson arrivals, and a tenant mix where each tenant's requests share
    a system prompt (``fleet_trace`` — deterministic in its seed and
    identical regardless of replica count).

    Baseline framing — read before comparing numbers.  The baseline is
    ONE replica-sized engine (same slots, same fused-decode width) given
    the fleet's ENTIRE KV budget (replicas x blocks-per-replica): equal
    total KV bytes, 1/N the decode lanes.  That is the horizontal
    scale-out question — the compressed engine already saturates a
    single mesh slice, so extra throughput must come from more replicas,
    not a wider batch.  All three targets are driven as ReplicaSets (the
    baseline is a 1-replica set) so busy time is accounted identically.

    Throughput is reported two ways.  ``tok_per_s`` is host wall time —
    honest for THIS process, where every replica steps on the same
    in-process loop (and on a 1-core CI runner they also share the
    core, so host numbers cannot show scale-out).  The headline
    ``tok_per_s_critical_path`` divides by the fleet's makespan — max
    per-replica busy time plus routing/rebalance time, each replica's
    jitted steps timed for real — which is the wall time an N-slice
    deployment sees, since replicas run concurrently on disjoint mesh
    slices (``make_replica_meshes``).  The CI gate holds the
    prefix-routed fleet to >= 1.5x the baseline on that metric, and to
    a prefix-cache hit rate >= round-robin's: prefix routing partitions
    tenants across replicas so N small caches behave like one big
    cache, while round-robin interleaves every tenant through every
    replica and LRU-thrashes all of them.
    """
    from repro.serving import ReplicaSet, fleet_trace
    R, S, NB = args.replicas, args.fleet_slots, args.fleet_blocks
    max_len = args.fleet_sys_len + args.fleet_prompt_max + args.fleet_gen_max
    trace = fleet_trace(
        n_requests=args.fleet_requests, n_tenants=args.fleet_tenants,
        vocab=cfg.vocab, sys_len=args.fleet_sys_len,
        rate_per_s=args.fleet_rate, burst_mean=4.0,
        prompt_median=8, prompt_sigma=0.6, prompt_max=args.fleet_prompt_max,
        gen_median=6, gen_sigma=1.1, gen_max=args.fleet_gen_max,
        seed=args.seed + 11)
    total_prompt = sum(len(t.prompt) for t in trace)
    kw = dict(kv_layout="paged", kv_dtype=args.kv_dtype,
              block_size=args.block_size, max_len=max_len,
              prefix_caching=True, max_queue=args.max_queue,
              token_budget=args.token_budget or 64)

    def build(n_replicas, routing, blocks, name):
        tracers = None
        if getattr(args, "trace_out", None):
            tracers = [_make_tracer(args, f"fleet/{name}/r{i}")
                       for i in range(n_replicas)]
        return ReplicaSet(cfg, params, n_replicas=n_replicas,
                          routing=routing, n_slots=S, n_blocks=blocks,
                          steal_threshold=args.fleet_steal_threshold,
                          tracers=tracers,
                          router_tracer=_make_router_tracer(
                              args, f"fleet/{name}/router"), **kw)

    out = {"n_replicas": R, "n_requests": args.fleet_requests,
           "n_tenants": args.fleet_tenants, "sys_len": args.fleet_sys_len,
           "prompt_max": args.fleet_prompt_max,
           "gen_max": args.fleet_gen_max, "prompt_tokens": total_prompt,
           "slots_per_replica": S, "blocks_per_replica": NB,
           "equal_total_kv_blocks": R * NB,
           "block_size": args.block_size}
    streams = {}
    prev = None
    for name, n_rep, routing, blocks in (
            ("single", 1, "round_robin", R * NB),
            ("round_robin", R, "round_robin", NB),
            ("prefix", R, "prefix", NB)):
        target = build(n_rep, routing, blocks, name)
        if n_rep == R and prev is not None:
            _adopt_compiled(prev, target)      # same shapes: reuse compiles
        summary, streams[name] = _fleet_warm_and_replay(
            target, trace, args.fleet_time_scale)
        pc = summary.get("prefix_cache", {})
        summary["prefix_hit_rate"] = (pc.get("hit_tokens", 0)
                                      / max(total_prompt, 1))
        out[name] = summary
        if n_rep == R:
            prev = target
        print(format_summary(f"fleet/{name}", summary)
              + f" | crit {summary['tok_per_s_critical_path']:.0f} tok/s"
              + f" | prefix-hit {summary['prefix_hit_rate']:.3f}")

    base = out["single"]["tok_per_s_critical_path"]
    for name in ("round_robin", "prefix"):
        out[name]["speedup_vs_baseline"] = (
            out[name]["tok_per_s_critical_path"] / base if base > 0
            else float("nan"))
        out[name]["token_identical"] = streams[name] == streams["single"]
    out["token_identical"] = all(out[n]["token_identical"]
                                 for n in ("round_robin", "prefix"))
    print(f"fleet @ {R} replicas, equal total KV ({R * NB} blocks): "
          f"critical-path speedup prefix="
          f"{out['prefix']['speedup_vs_baseline']:.2f}x "
          f"round_robin={out['round_robin']['speedup_vs_baseline']:.2f}x; "
          f"prefix-hit prefix={out['prefix']['prefix_hit_rate']:.3f} "
          f"round_robin={out['round_robin']['prefix_hit_rate']:.3f}; "
          f"steals={out['prefix']['n_steals']} "
          f"token-identical={out['token_identical']}")
    return out


def _digest(name: str, s: dict | None) -> dict | None:
    """One scenario's machine-comparable one-liner for the summary block.
    NaNs become None so the summary stays strict-JSON diffable."""
    if not s or "tok_per_s" not in s:
        return None
    import math

    def num(x):
        return None if x is None or (isinstance(x, float)
                                     and math.isnan(x)) else x
    d = {"tok_per_s": num(s.get("tok_per_s")),
         "itl_p50": num(s.get("itl", {}).get("p50")),
         "itl_p99": num(s.get("itl", {}).get("p99")),
         "line": format_summary(name, s)}
    sp = s.get("speculative")
    if sp:
        d["spec_acceptance_rate"] = num(sp.get("acceptance_rate"))
        d["spec_accepted_per_step"] = num(sp.get("accepted_per_step"))
    if "speedup_vs_baseline" in s:
        d["speedup_vs_baseline"] = num(s["speedup_vs_baseline"])
    if "token_identical" in s:
        d["token_identical"] = s["token_identical"]
    return d


def summary_block(sections: dict) -> dict:
    """Top-level ``summary`` for BENCH_serving.json: per-scenario digests
    plus aggregate tok/s and worst-case ITL tails, so the bench trajectory
    diffs across PRs without walking every per-scenario tree."""
    scenarios = {}
    for name, s in sections.items():
        d = _digest(name, s)
        if d is not None:
            scenarios[name] = d
    rates = [d["tok_per_s"] for d in scenarios.values()
             if d["tok_per_s"] is not None]
    p50s = [d["itl_p50"] for d in scenarios.values()
            if d["itl_p50"] is not None]
    p99s = [d["itl_p99"] for d in scenarios.values()
            if d["itl_p99"] is not None]
    return {
        "n_scenarios": len(scenarios),
        "tok_per_s_mean": sum(rates) / len(rates) if rates else None,
        "tok_per_s_max": max(rates, default=None),
        "itl_p50_worst": max(p50s, default=None),
        "itl_p99_worst": max(p99s, default=None),
        "scenarios": scenarios,
    }


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-paper")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + short workload (CI / CPU)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--token-budget", type=int, default=None,
                    help="prefill tokens per engine step (chunked prefill); "
                         "default: engine default (effectively un-chunked)")
    ap.add_argument("--max-prefill-per-step", type=int, default=None,
                    help="DEPRECATED request-count spelling of --token-budget")
    ap.add_argument("--time-scale", type=float, default=1.0)
    ap.add_argument("--kv-layout", default="both",
                    choices=("slot", "paged", "both"))
    ap.add_argument("--kv-dtype", default="bf16", choices=("bf16", "int8"),
                    help="KV arena storage dtype for the main grid engines "
                         "(the kv-dtype scenario always runs both)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--mesh", default=None,
                    help="serving mesh 'DATAxMODEL' (e.g. '1x8') — "
                         "mesh-native tensor-parallel engine; default: "
                         "single device")
    ap.add_argument("--weight-pattern", default="8:16")
    ap.add_argument("--outlier-pattern", default="16:256")
    ap.add_argument("--seed", type=int, default=0)
    # shared-system-prompt scenario
    ap.add_argument("--no-shared-prefix", action="store_true",
                    help="skip the shared-system-prompt scenario")
    ap.add_argument("--shared-requests", type=int, default=16)
    ap.add_argument("--sys-len", type=int, default=96)
    ap.add_argument("--tail-len", type=int, default=16)
    ap.add_argument("--kv-budget-tokens", type=int, default=None,
                    help="KV budget for the shared-prefix comparison "
                         "(default: slots * max_len)")
    # equal-HBM-budget KV dtype scenario
    ap.add_argument("--no-kv-dtype", action="store_true",
                    help="skip the equal-HBM bf16-vs-int8 KV scenario")
    ap.add_argument("--kv-dtype-slots", type=int, default=8,
                    help="bf16 slot count of the equal-HBM comparison; the "
                         "int8 engine gets the same bytes' worth of slots")
    # long-prompt chunked-prefill scenario
    ap.add_argument("--no-long-prompt", action="store_true",
                    help="skip the long-prompt chunked-prefill scenario")
    # mixed-family co-hosting scenario
    ap.add_argument("--no-mixed-family", action="store_true",
                    help="skip the mixed-family (xlstm + dense) scenario")
    # speculative-decoding scenario
    ap.add_argument("--no-speculative", action="store_true",
                    help="skip the speculative-decoding scenario")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="initial draft length for the speculative "
                         "scenario")
    ap.add_argument("--long-requests", type=int, default=2)
    ap.add_argument("--long-short-requests", type=int, default=6)
    ap.add_argument("--long-len", type=int, default=256,
                    help="long-prompt length for the chunked scenario")
    # multi-replica fleet scenario
    ap.add_argument("--no-fleet", action="store_true",
                    help="skip the multi-replica fleet scenario")
    ap.add_argument("--replicas", type=int, default=4,
                    help="fleet scenario replica count")
    ap.add_argument("--fleet-requests", type=int, default=32)
    ap.add_argument("--fleet-tenants", type=int, default=8,
                    help="tenants (distinct shared system prompts)")
    ap.add_argument("--fleet-slots", type=int, default=4,
                    help="decode slots per replica (the single baseline "
                         "gets the same)")
    ap.add_argument("--fleet-blocks", type=int, default=32,
                    help="KV blocks per replica; the single baseline "
                         "gets replicas * this (equal total KV)")
    ap.add_argument("--fleet-sys-len", type=int, default=32)
    ap.add_argument("--fleet-prompt-max", type=int, default=24)
    ap.add_argument("--fleet-gen-max", type=int, default=48)
    ap.add_argument("--fleet-rate", type=float, default=50.0,
                    help="fleet trace Poisson burst-epoch rate, /s")
    ap.add_argument("--fleet-steal-threshold", type=int, default=2)
    ap.add_argument("--fleet-time-scale", type=float, default=0.002,
                    help="arrival time compression for the fleet replays "
                         "(bursty near-saturation is the scenario)")
    # very-long-prompt prefill curve (slow; opt-in)
    ap.add_argument("--prefill-curve", action="store_true",
                    help="SLOW: record prefill-time-vs-prompt-length "
                         "curves (retired gathered path vs in-place "
                         "attend-over-pool) into the results file")
    ap.add_argument("--curve-lens", default="128,256,512,1024",
                    help="comma-separated prompt lengths for "
                         "--prefill-curve")
    ap.add_argument("--curve-chunk", type=int, default=64,
                    help="chunk size for --prefill-curve")
    ap.add_argument("--curve-reps", type=int, default=3,
                    help="timed repetitions per --prefill-curve point")
    ap.add_argument("--out", default="BENCH_serving.json",
                    help="machine-readable results file ('' to skip)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace_event JSON of all "
                         "engines here (load in ui.perfetto.dev); a "
                         "Prometheus counter snapshot lands next to it")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests = min(args.requests, 10)
        args.rate = max(args.rate, 5.0)
        args.gen = min(args.gen, 8)
        args.slots = min(args.slots, 4)
        args.max_len = min(args.max_len, 64)
        args.block_size = min(args.block_size, 8)
        args.shared_requests = min(args.shared_requests, 10)
        args.sys_len = min(args.sys_len, 40)
        args.tail_len = min(args.tail_len, 8)
        args.long_len = min(args.long_len, 128)
        args.long_requests = min(args.long_requests, 1)
        args.long_short_requests = min(args.long_short_requests, 4)
        args.replicas = min(args.replicas, 4)
        args.fleet_requests = min(args.fleet_requests, 32)
        args.fleet_sys_len = min(args.fleet_sys_len, 32)
        args.fleet_gen_max = min(args.fleet_gen_max, 48)
        args.curve_lens = "64,128"
        args.curve_chunk = min(args.curve_chunk, 16)
        args.curve_reps = 1

    cfg = bench_cfg(args)
    zoo = get_model(cfg)
    params = zoo.init(jax.random.PRNGKey(args.seed))

    trace = poisson_trace(n_requests=args.requests, rate_per_s=args.rate,
                          vocab=cfg.vocab,
                          prompt_len=(args.prompt_min, args.prompt_max),
                          max_new_tokens=args.gen, seed=args.seed)
    layouts = (("slot", "paged") if args.kv_layout == "both"
               else (args.kv_layout,))
    print(f"model {cfg.name} ({cfg.family}), {args.requests} requests @ "
          f"{args.rate}/s Poisson, prompts {args.prompt_min}-{args.prompt_max}, "
          f"gen {args.gen}, {args.slots} slots, layouts {layouts}")

    results = {}
    for layout in layouts:
        results[f"dense/{layout}"] = run_one(f"dense/{layout}", cfg, params,
                                             trace, args, layout)

    scfg = SparsifyConfig(weight_pattern=args.weight_pattern,
                          outlier_pattern=args.outlier_pattern,
                          scorer="magnitude", use_smoothquant=False)
    sparams, report = sparsify_for_serving(params, scfg)
    print(f"  sparse deploy: {report['n_layers_sparsified']} matrices, "
          f"{report['ratio']:.3f}x bytes")
    for layout in layouts:
        results[f"sparse/{layout}"] = run_one(f"sparse/{layout}", cfg,
                                              sparams, trace, args, layout)

    d = results.get("dense/slot") or results.get(f"dense/{layouts[0]}")
    s = results.get("sparse/slot") or results.get(f"sparse/{layouts[0]}")
    if d and s and d["tok_per_s"] > 0:
        print(f"sparse/dense throughput: {s['tok_per_s']/d['tok_per_s']:.2f}x")

    shared = None
    if not args.no_shared_prefix:
        shared = shared_prefix_scenario(cfg, params, args)

    kv_dtype = None
    if not args.no_kv_dtype:
        kv_dtype = kv_dtype_scenario(cfg, params, args)

    long_prompt = None
    if not args.no_long_prompt:
        long_prompt = long_prompt_scenario(cfg, params, args)

    mixed_family = None
    if not args.no_mixed_family:
        mixed_family = mixed_family_scenario(args)

    speculative = None
    if not args.no_speculative:
        speculative = speculative_scenario(cfg, args)

    fleet = None
    if not args.no_fleet:
        fleet = fleet_scenario(cfg, params, args)

    prefill_curve = None
    if args.prefill_curve:
        prefill_curve = prefill_curve_scenario(cfg, params, args)

    if args.out:
        payload = {
            "meta": {"model": cfg.name, "family": cfg.family,
                     "smoke": args.smoke, "requests": args.requests,
                     "rate_per_s": args.rate, "gen": args.gen,
                     "slots": args.slots, "max_len": args.max_len,
                     "block_size": args.block_size,
                     "kv_dtype": args.kv_dtype,
                     "token_budget": args.token_budget,
                     "weight_pattern": args.weight_pattern,
                     "outlier_pattern": args.outlier_pattern,
                     "seed": args.seed, "timestamp": time.time(),
                     "backend": jax.default_backend(),
                     "visible_devices": jax.device_count(),
                     "mesh": args.mesh},
            "poisson": results,
            "shared_prefix": shared,
            "kv_dtype": kv_dtype,
            "long_prompt": long_prompt,
            "mixed_family": mixed_family,
            "speculative": speculative,
            "fleet": fleet,
            "prefill_curve": prefill_curve,
        }
        sections = dict(results)
        if shared:
            sections["shared_prefix/slot"] = shared.get("slot")
            sections["shared_prefix/paged"] = shared.get("paged")
        if kv_dtype:
            sections["kv_dtype/bf16"] = kv_dtype.get("bf16")
            sections["kv_dtype/int8"] = kv_dtype.get("int8")
        if long_prompt:
            sections["long_prompt/oneshot"] = long_prompt.get("oneshot")
            sections["long_prompt/chunked"] = long_prompt.get("chunked")
        if mixed_family:
            sections["mixed_family"] = mixed_family
        if speculative:
            for v in ("baseline", "sparse_draft", "ngram_draft"):
                sections[f"speculative/{v}"] = speculative.get(v)
        if fleet:
            for v in ("single", "round_robin", "prefix"):
                sections[f"fleet/{v}"] = fleet.get(v)
        payload["summary"] = summary_block(sections)
        if _OBS["registry"] is not None:
            payload["counters"] = _OBS["registry"].snapshot()
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.out}")

    if args.trace_out and _OBS["buffer"] is not None:
        _OBS["buffer"].write(args.trace_out)
        counters = args.trace_out + ".counters.txt"
        with open(counters, "w") as f:
            f.write(_OBS["registry"].prometheus_text())
        print(f"wrote {args.trace_out} (load in ui.perfetto.dev) "
              f"and {counters}")
    return results


if __name__ == "__main__":
    main()
