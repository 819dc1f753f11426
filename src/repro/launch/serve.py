"""Serving driver: thin CLI over the continuous-batching engine.

The sparse path is the paper's deployment story: linear weights are replaced
by their 8:16 (+N:256 outlier) compressed form at load time
(models/sparse_serving.py).  Every backend serves them through
``sparse_apply``, which rebuilds each dense weight from the compressed
buffers by a one-hot einsum inside the step; the Pallas kernels in
``kernels/`` are not on the served path yet.

Modes:
  default      continuous-batching engine (serving/) for every zoo family —
               dense/moe/ssm/hybrid/encdec ride their family adapters
               (serving/families.py); vlm keeps the one-shot loop.  The
               enc-dec family feeds each request's encoder features at
               submit time (here: random frontend embeddings).
  --legacy     DEPRECATED parity-check adapter: runs the one-shot lock-step
               loop, then (greedy, engine-supported family) replays the
               same prompts through the engine and verifies the token
               streams are identical.  Still the only path for vlm.
  --trace F    replay a JSON request trace (serving/trace.py) through the
               engine and report tok/s + latency percentiles.

``--kv-layout paged`` swaps the per-request max_len reservation for the
paged block pool (serving/paged/): block-granular allocation, prefix-cache
sharing of identical prompt prefixes, preempt-to-queue under KV pressure.
Token-identical to ``--kv-layout slot`` for the same requests and seeds.

``--token-budget N`` bounds the prefill tokens any engine step may spend:
prompts longer than N advance chunk-by-chunk across steps while everyone
else keeps decoding (chunked prefill — token-identical to the un-chunked
engine).  ``--max-prefill-per-step`` is the deprecated request-count
spelling of the same knob.

``--mesh 1x8`` serves mesh-native (serving/placement.py): compressed (and
dense) weights tensor-parallel over the "model" axis, KV arenas sharded by
head, explicit shardings on every jitted step.  Token-identical to the
single-device engine.  On CPU, force host devices first:
XLA_FLAGS=--xla_force_host_platform_device_count=8.

``--draft {ngram,sparse,self}`` turns on draft-verify speculative
decoding (serving/speculative.py): ``sparse`` is the paper's deployment
twist — the 8:16 + outlier compressed model drafts ``--spec-k`` tokens
per request per step for its dense counterpart, and the dense target
scores all k+1 positions in one fused verify call; ``ngram`` is the
model-free prompt-lookup proposer; ``self`` drafts with the target's own
params (an upper bound on acceptance, used by the parity tests).  Greedy
speculative streams are token-identical to non-speculative ones.

``--replicas N`` serves through N in-process engine replicas behind the
fleet router (serving/fleet/): every request is scored per replica on
prefix-cache hit potential, load, and session affinity
(``--routing prefix``; ``round_robin``/``least_loaded`` are the
baselines), with work-stealing rebalance between steps.  Each replica
gets its own ``--slots``/``--n-blocks`` pool; with ``--mesh`` the spec
is PER REPLICA and replicas take disjoint device slices
(``make_replica_meshes``); without it each replica gets a device of its
own when there are at least N.  Token streams are identical to a single
engine serving the same requests.

``--trace-out trace.json`` turns on the observability substrate
(serving/observe.py): a Chrome/Perfetto ``trace_event`` JSON of every
request lifecycle, engine step, jitted call and preemption (load the file
in ui.perfetto.dev), plus a Prometheus counter snapshot written next to
it.  Without the flag the engine runs with the no-op NULL_TRACER.

Example (CPU, the reduced smoke preset of the default arch):
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --smoke-arch --batch 4 --prompt-len 32 --gen 16 --sparse
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import get, get_smoke
from ..models import get_model, grow_caches
from ..core import SparsifyConfig
from ..runtime.compile_cache import use_compile_cache


def build_params(cfg, args):
    """Init the model (optionally deploying compressed sparse weights).
    Returns (zoo, params, key, sparsify report or None)."""
    zoo = get_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = zoo.init(key)
    report = None
    if args.sparse:
        from ..models.sparse_serving import sparsify_for_serving
        scfg = SparsifyConfig(weight_pattern=args.weight_pattern,
                              outlier_pattern=args.outlier_pattern,
                              scorer="magnitude", use_smoothquant=False)
        params, report = sparsify_for_serving(params, scfg)
        print(f"sparse deploy: {report['n_layers_sparsified']} matrices, "
              f"bytes {report['dense_bytes']/2**20:.1f}MiB -> "
              f"{report['compressed_bytes']/2**20:.1f}MiB "
              f"({report['ratio']:.3f}x)")
    return zoo, params, key, report


def run_oneshot(cfg, zoo, params, key, args):
    """Legacy lock-step loop: batched prefill, then decode the whole batch
    one token at a time.  Supports every model family."""
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab)
    capacity = args.prompt_len + args.gen
    batch = {"tokens": prompt}
    if cfg.family in ("vlm", "encdec"):
        batch["embeds"] = jax.random.normal(key, (args.batch, args.prompt_len,
                                                  cfg.d_model), jnp.float32)
        if cfg.family == "vlm":
            pos = jnp.broadcast_to(jnp.arange(args.prompt_len)[None, None],
                                   (3, args.batch, args.prompt_len))
            batch["positions"] = pos
            del batch["tokens"]

    t0 = time.time()
    logits, caches = zoo.prefill(params, batch)
    # reserve decode headroom in every family's cache layout up front
    caches = grow_caches(caches, capacity)
    prefill_s = time.time() - t0

    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    for _ in range(args.gen - 1):
        logits, caches = zoo.decode(params, caches, {"tokens": tok})
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    gen = jnp.concatenate(out_tokens, axis=1)
    decode_s = time.time() - t0
    print(f"prefill {args.batch}x{args.prompt_len} in {prefill_s:.2f}s; "
          f"decoded {args.gen} tokens in {decode_s:.2f}s "
          f"({args.batch*(args.gen-1)/max(decode_s,1e-9):.1f} tok/s)")
    return gen


def _engine_kwargs(args) -> dict:
    return dict(n_slots=args.slots, max_queue=args.max_queue,
                token_budget=args.token_budget,
                max_prefill_per_step=args.max_prefill_per_step,
                kv_layout=args.kv_layout, kv_dtype=args.kv_dtype,
                block_size=args.block_size,
                n_blocks=args.n_blocks,
                prefix_caching=not args.no_prefix_cache)


def _make_draft(cfg, params, args):
    """A SpeculativeConfig from --draft/--spec-k, or None.

    ``sparse`` sparsifies a fresh dense init with the run's compression
    settings (the 8:16 model drafting for its dense counterpart); with
    --sparse the target IS that model, so the draft degenerates to
    self-drafting, which is still a valid (if pointless) configuration.
    """
    if args.draft in (None, "none"):
        return None
    from ..serving import SpeculativeConfig
    max_k = max(8, args.spec_k)
    if args.draft == "ngram":
        return SpeculativeConfig(k=args.spec_k, max_k=max_k, method="ngram")
    if args.draft == "self":
        dparams = params
    else:
        from ..models.sparse_serving import sparsify_for_serving
        dense = get_model(cfg).init(jax.random.PRNGKey(args.seed))
        scfg = SparsifyConfig(weight_pattern=args.weight_pattern,
                              outlier_pattern=args.outlier_pattern,
                              scorer="magnitude", use_smoothquant=False)
        dparams, _ = sparsify_for_serving(dense, scfg)
    return SpeculativeConfig(k=args.spec_k, max_k=max_k, method="model",
                             params=dparams, cfg=cfg)


def _print_spec_stats(engine) -> None:
    sp = engine.stats().get("speculative")
    if sp:
        print(f"  speculative[{sp['method']} k={sp['k']}]: "
              f"acceptance {sp['acceptance_rate']:.2f}, "
              f"{sp['accepted_per_step']:.2f} accepted tok/step "
              f"({sp['accepted']}/{sp['drafted']} over "
              f"{sp['n_spec_steps']} steps)")


def _make_tracer(args):
    """A ServingTracer when --trace-out was given, else None (the engine
    then runs with NULL_TRACER: zero observability cost)."""
    if not getattr(args, "trace_out", None):
        return None
    from ..serving import ServingTracer
    return ServingTracer()


def _make_fleet_tracers(args, n: int):
    """Per-replica ServingTracers sharing ONE buffer + registry (each
    replica gets its own pid track in the merged Perfetto file) plus the
    RouterTracer for routing-decision instants, when --trace-out was
    given; (None, None) otherwise."""
    if not getattr(args, "trace_out", None):
        return None, None
    from ..serving import RouterTracer, ServingTracer
    first = ServingTracer(name="r0")
    tracers = [first] + [
        ServingTracer(buffer=first.buffer, registry=first.registry,
                      name=f"r{i}") for i in range(1, n)]
    router = RouterTracer(buffer=first.buffer, registry=first.registry,
                          name="router")
    return tracers, router


def _build_target(cfg, params, args, *, max_len):
    """The serving target: one engine, or ``--replicas N`` of them behind
    the prefix-aware router.  ReplicaSet duck-types the engine surface
    (submit/step/run/has_work/finished/stats), so run/replay drive either.
    Returns (target, tracer-to-write)."""
    from .mesh import make_replica_meshes, make_serving_mesh
    draft = _make_draft(cfg, params, args)
    kw = _engine_kwargs(args)
    if args.replicas == 1:
        mesh = make_serving_mesh(args.mesh)
        if mesh is not None:
            print(f"serving mesh: {dict(mesh.shape)} "
                  f"({mesh.devices.size} devices, {jax.default_backend()})")
        from ..serving import ServingEngine
        tracer = _make_tracer(args)
        return ServingEngine(cfg, params, max_len=max_len, tracer=tracer,
                             draft=draft, mesh=mesh, **kw), tracer
    from ..serving import ReplicaSet
    meshes = make_replica_meshes(args.mesh, args.replicas)
    if meshes[0] is not None:
        print(f"fleet meshes: {args.replicas} x {dict(meshes[0].shape)} "
              f"(disjoint slices, {jax.default_backend()})")
    tracers, router_tracer = _make_fleet_tracers(args, args.replicas)
    fleet = ReplicaSet(cfg, params, n_replicas=args.replicas,
                       routing=args.routing, meshes=meshes, tracers=tracers,
                       router_tracer=router_tracer, max_len=max_len,
                       draft=draft, **kw)
    return fleet, (tracers[0] if tracers else None)


def _print_fleet_stats(target) -> None:
    st = target.stats()
    if "n_replicas" not in st:
        return
    pc = st["prefix_cache"]
    per = [f"r{i}: {p['n_finished']} done, {p['n_steps']} steps"
           for i, p in enumerate(st["replicas"])]
    print(f"  fleet[{st['routing']}]: {st['n_replicas']} replicas, "
          f"{st['n_steals']} steals, {st['n_drains']} drains, "
          f"prefix-hit {pc['hit_rate']:.2f} | " + "; ".join(per))


def _write_observability(tracer, args) -> None:
    """Write the Perfetto trace and the Prometheus counter snapshot next
    to it (<trace-out> and <trace-out>.counters.txt)."""
    if tracer is None:
        return
    tracer.write_trace(args.trace_out)
    counters = args.trace_out + ".counters.txt"
    with open(counters, "w") as f:
        f.write(tracer.counters_text())
    print(f"trace written to {args.trace_out} (load in ui.perfetto.dev); "
          f"counters in {counters}")


def run_engine(cfg, params, key, args, quiet: bool = False):
    """Continuous-batching engine (or --replicas N fleet) on a batch of
    random prompts."""
    from ..serving import SamplingParams
    engine, tracer = _build_target(cfg, params, args,
                                   max_len=args.prompt_len + args.gen)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab)
    # enc-dec requests carry their encoder features (same draw as the
    # one-shot loop, so --legacy parity compares like against like)
    embeds = jax.random.normal(key, (args.batch, args.prompt_len,
                                     cfg.d_model), jnp.float32) \
        if cfg.family == "encdec" else None
    sp = SamplingParams(max_new_tokens=args.gen,
                        temperature=args.temperature, top_k=args.top_k)
    t0 = time.time()
    reqs = [engine.submit(prompt[i], sp,
                          embeds=None if embeds is None else embeds[i])
            for i in range(args.batch)]
    engine.run()
    wall = time.time() - t0
    n_tok = sum(len(r.tokens) for r in reqs)
    if not quiet:
        print(f"engine[{args.kv_layout}]: {args.batch} requests, {n_tok} "
              f"tokens in {wall:.2f}s ({n_tok/max(wall,1e-9):.1f} tok/s, "
              f"{engine.stats()['n_steps']} steps, {args.slots} slots)")
        if args.kv_layout == "paged" and args.replicas == 1:
            print(f"  paged: {engine.stats()['pool']}")
        _print_fleet_stats(engine)
        _print_spec_stats(engine)
    _write_observability(tracer, args)
    return jnp.asarray([r.tokens for r in reqs], jnp.int32)


def run_trace(cfg, params, args):
    """Replay a recorded request trace through the engine (or fleet)."""
    from ..runtime.metrics import format_summary, summarize
    from ..serving import load_trace, replay
    engine, tracer = _build_target(cfg, params, args, max_len=args.max_len)
    trace = load_trace(args.trace)
    res = replay(engine, trace, time_scale=args.time_scale)
    summary = summarize([r.metrics for r in res["finished"]], res["wall_s"])
    print(format_summary("trace", summary))
    _print_fleet_stats(engine)
    _print_spec_stats(engine)
    if res["rejected"]:
        print(f"rejected by admission control: {res['rejected']}")
    _write_observability(tracer, args)
    return res


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI; ``chip_smoke.py`` builds its runs from it too."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke-arch", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--sparse", action="store_true",
                    help="deploy 8:16 + 16:256-outlier compressed weights")
    ap.add_argument("--weight-pattern", default="8:16")
    ap.add_argument("--outlier-pattern", default="16:256")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legacy", action="store_true",
                    help="one-shot lock-step loop instead of the engine")
    ap.add_argument("--slots", type=int, default=8,
                    help="engine KV-pool slots (concurrent requests); "
                         "per replica with --replicas")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve with N in-process engine replicas behind "
                         "the fleet router (serving/fleet/); each replica "
                         "gets its own --slots/--n-blocks pool and, with "
                         "--mesh, its own disjoint device slice")
    ap.add_argument("--routing", default="prefix",
                    choices=("prefix", "round_robin", "least_loaded"),
                    help="fleet routing policy: 'prefix' scores cached-"
                         "prompt fraction minus load plus session "
                         "affinity; baselines cycle or pick the emptiest")
    ap.add_argument("--kv-layout", default="slot", choices=("slot", "paged"),
                    help="contiguous per-slot KV vs paged block pool")
    ap.add_argument("--kv-dtype", default="bf16", choices=("bf16", "int8"),
                    help="KV arena storage dtype; int8 stores per-position "
                         "per-KV-head scales and dequantizes inside "
                         "attention (~1.9x more context per HBM byte)")
    ap.add_argument("--mesh", default=None,
                    help="serving mesh 'DATAxMODEL' (e.g. '1x8'; bare '8' = "
                         "model-only TP) — tensor-parallel compressed "
                         "forward + sharded KV arenas; default: no mesh "
                         "(single device)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged layout)")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="paged arena size in blocks (default: the same "
                         "HBM as the slot layout would reserve)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable prefix-cache block sharing (paged)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="prefill tokens any engine step may spend; prompts "
                         "longer than this advance chunk-by-chunk across "
                         "steps beside the decode batch (default: 2x the "
                         "KV capacity, i.e. effectively un-chunked)")
    ap.add_argument("--max-prefill-per-step", type=int, default=None,
                    help="DEPRECATED: request-count interleave bound; "
                         "aliased to --token-budget N*capacity")
    ap.add_argument("--draft", default="none",
                    choices=("none", "ngram", "sparse", "self"),
                    help="speculative-decoding proposer: 'sparse' drafts "
                         "with the 8:16+outlier compressed model, 'ngram' "
                         "with prompt-lookup, 'self' with the target's own "
                         "params (parity/upper-bound runs)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="initial draft tokens per request per step (each "
                         "request's k then adapts to its own observed "
                         "acceptance)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--trace", default=None,
                    help="JSON request trace to replay through the engine")
    ap.add_argument("--max-len", type=int, default=256,
                    help="per-slot KV capacity (trace mode)")
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="compress (<1) / stretch (>1) trace arrival gaps")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "run here (load in ui.perfetto.dev); a Prometheus "
                         "counter snapshot lands next to it")
    return ap


def main(argv=None):
    use_compile_cache()
    ap = build_parser()
    args = ap.parse_args(argv)

    from ..serving import SUPPORTED_FAMILIES
    cfg = get_smoke(args.arch) if args.smoke_arch else get(args.arch)
    if args.trace is not None and args.legacy:
        ap.error("--trace replays through the engine; drop --legacy")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.replicas > 1 and args.legacy:
        ap.error("--replicas needs the engine path; drop --legacy")
    if args.trace is not None and cfg.family not in SUPPORTED_FAMILIES:
        ap.error(f"--trace replays through the engine, which serves "
                 f"{SUPPORTED_FAMILIES} families; {args.arch!r} is "
                 f"{cfg.family!r}")
    if args.trace is not None and cfg.family == "encdec":
        ap.error("--trace carries token prompts only; the enc-dec family "
                 "needs per-request encoder features")

    zoo, params, key, _ = build_params(cfg, args)

    if args.trace is not None:
        return run_trace(cfg, params, args)

    if args.legacy or cfg.family not in SUPPORTED_FAMILIES:
        if args.legacy:
            print("--legacy is DEPRECATED: the engine serves every zoo "
                  "family except vlm; running the one-shot loop as a "
                  "parity check")
        else:
            print(f"family {cfg.family!r} is not engine-served; "
                  f"using the one-shot loop")
        gen = run_oneshot(cfg, zoo, params, key, args)
        if (args.legacy and cfg.family in SUPPORTED_FAMILIES
                and args.temperature == 0):
            import numpy as np
            eng = run_engine(cfg, params, key, args, quiet=True)
            if np.array_equal(np.asarray(gen), np.asarray(eng)):
                print("legacy parity: engine token streams identical")
            elif cfg.dtype == jnp.float32:
                raise SystemExit("legacy parity FAILED: engine and one-shot "
                                 "token streams differ")
            else:
                # sub-f32 dtypes: XLA rounds fused low-precision chains at
                # shape-dependent fusion boundaries, so the jitted engine
                # step and the eager one-shot loop can disagree by one ulp
                # — enough to flip greedy argmax on a near-tie.  Bit-exact
                # parity is asserted at f32 (tests/test_family_engines.py).
                n_bad = int((np.asarray(gen) != np.asarray(eng))
                            .any(axis=1).sum())
                print(f"legacy parity: {n_bad}/{args.batch} streams diverge "
                      f"(greedy near-ties under {np.dtype(cfg.dtype).name} "
                      f"fusion rounding; rerun an f32 config for the "
                      f"bit-exact check)")
    else:
        gen = run_engine(cfg, params, key, args)
    print("sample:", gen[0, :12].tolist())
    return gen


if __name__ == "__main__":
    main()
