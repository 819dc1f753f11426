"""Serve internlm2-1.8b at its published widths on one TPU chip, end to end.

Drives the serving engine's main path once, through the entry points a
user calls (``launch/serve.py``'s ``build_params`` and ``_build_target``,
then ``submit``/``run``), with random weights made from ``--seed``:

  A  dense weights, slot KV: every request finishes, and each prompt's
     last-position logits from the engine's own compiled chunk step match
     a plain forward of the same prompt (``zoo.prefill``);
  B  8:16 + 16:256 compressed weights (``sparsify_for_serving``), paged KV
     through the compiled Pallas paged-attention kernel, against the same
     compressed params served with slot KV (XLA attention), at the chunk
     step and at every decode step on identical inputs; a control plants a
     one-block attention fault, which must fail the same bound; on the
     chip every projection must take the sparse-linear kernel, by the
     tracer's ``serving_sparse_projections_total``;
  C  the sparse linear's kernel branch (``sparse_apply_pallas``, compiled)
     against its portable branch (``sparse_apply_portable``), by name, at
     internlm2's ``w_up`` and Mistral-7B's ``w_gate`` shapes, bf16 and int8
     values, with and without outliers, at 8, 32 and 2048 rows; and the
     time of one projection on each branch, in the served format.

``--four-chip`` runs only the multi-chip paths instead, on four chips:

  D  the model under ``--mesh 1x4`` (8 KV heads over 4 chips) against the
     single-device engine, and ``--replicas 4`` (one chip per replica
     behind the prefix router) against one engine, both at the chunk step
     and at every decode step on identical inputs.

Each phase prints one line with its numbers.  Any failure exits non-zero
before the last line, which is one JSON object naming the device JAX ran
on.  One process, no children: the chip belongs to this process.

    python3 chip_smoke.py               # phases A-C, one chip
    python3 chip_smoke.py --four-chip   # phase D, four chips
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from repro import configs                                      # noqa: E402
from repro.core import SparsifyConfig                          # noqa: E402
from repro.launch import serve                                 # noqa: E402
from repro.models.sparse_serving import (_to_sparse_weight,    # noqa: E402
                                         sparse_apply_pallas,
                                         sparse_apply_portable)
from repro.runtime.compile_cache import use_compile_cache      # noqa: E402
from repro.serving import SamplingParams, Status               # noqa: E402

# Logit tolerance of phases A, B and D: max |got - want| <= LOGIT_TOL *
# max |want| over every compared vocabulary row.  Both sides are bf16 end to
# end (weights, activations, the logits themselves) and the two programs
# reduce in different orders (blocked online softmax against one softmax,
# other fusions, other batch sizes), which no ulp count bounds over 24
# layers, so the bound is set from measurement: on a v5e every comparison
# of this script lands at 0.013-0.017, and the bound leaves three times
# that.  It is not loose enough to hide a fault: phase B's control plants a
# one-block attention fault and fails unless it exceeds the bound (at
# internlm2 widths with 2 layers on the CPU it measures 0.26).
LOGIT_TOL = 0.05
# Kernel tolerance of phase C, on the same measure: both branches multiply
# the same bf16 weight (an int8 value scaled and rounded to bf16 once on
# both) by the same bf16 rows, and round the output to bf16 once (2^-8).
KERNEL_TOL = 0.02


class SmokeFailure(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Preset:
    cfg: object
    n_requests: int
    prompt_len: int
    gen: int
    kernel_shapes: tuple[tuple[int, int], ...]   # [out, in] of phase C
    kernel_batches: tuple[int, ...]
    timed: bool = False                   # time each branch in phase C


def full_preset() -> Preset:
    """internlm2-1.8b at its published widths, depth and vocabulary; eight
    512-token prompts keep every engine at one chunk and one decode
    program."""
    cfg = configs.get("internlm2-1.8b")
    mistral = configs.get("mistral-7b")
    return Preset(cfg, n_requests=8, prompt_len=512, gen=32,
                  kernel_shapes=((cfg.d_ff, cfg.d_model),
                                 (mistral.d_ff, mistral.d_model)),
                  kernel_batches=(8, 32, 2048), timed=True)


def smoke_preset() -> Preset:
    """The registered smoke config, for the CPU test of these phases."""
    return Preset(configs.get_smoke("internlm2-1.8b"), n_requests=4,
                  prompt_len=16, gen=4, kernel_shapes=((256, 512),),
                  kernel_batches=(8,))


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _serve_args(p: Preset, seed: int, *extra: str):
    """The serve CLI's arguments for one engine of this preset: every
    request admitted in the first step, as one chunk group."""
    return serve.build_parser().parse_args([
        "--seed", str(seed), "--batch", str(p.n_requests),
        "--prompt-len", str(p.prompt_len), "--gen", str(p.gen),
        "--slots", str(p.n_requests),
        "--token-budget", str(p.n_requests * p.prompt_len), *extra])


def _prompts(p: Preset, seed: int) -> np.ndarray:
    key = jax.random.PRNGKey(seed + 1)
    return np.asarray(jax.random.randint(
        key, (p.n_requests, p.prompt_len), 0, p.cfg.vocab, jnp.int32))


def _record_logits(engine, chunk: dict, decode: dict) -> None:
    """Wrap the engine's compiled chunk step (the program
    ``serving/families.py`` builds) so that ``chunk[request_id]`` holds the
    f32 logits at the last position of the chunk that ends each prompt, and
    its decode step so that ``decode[request_id]`` lists the request's f32
    logits of each decode step, on the host."""
    adapter = engine.adapter
    step = adapter.step_chunk

    def recording(rows, lanes, cur, n_new, tokens):
        logits = step(rows, lanes, cur, n_new, tokens)
        n = len(rows)
        last = logits[jnp.arange(n), n_new[:n] - 1].astype(jnp.float32)
        for i, slot in enumerate(rows):
            chunk[engine.running[slot].request_id] = last[i]
        return logits

    step_decode = adapter.step_decode

    def recording_decode(tokens, active):
        logits = step_decode(tokens, active)
        rows = np.asarray(logits[jnp.asarray(active), 0], np.float32)
        for row, slot in zip(rows, active):
            decode.setdefault(engine.running[slot].request_id, []).append(row)
        return logits

    adapter.step_chunk = recording
    adapter.step_decode = recording_decode


@dataclasses.dataclass
class Served:
    """One engine's or fleet's greedy run of every prompt: per request its
    tokens, the chunk-step logits at its prompt's last position ([n, V]),
    and the logits of each decode step (per request [gen - 1, V])."""
    tokens: list
    chunk: np.ndarray
    decode: list
    wall: float


def _serve_recorded(target, p: Preset, prompts) -> Served:
    """``_serve`` with every logit row recorded; ``target`` is one engine
    or a fleet, whose every replica is recorded."""
    chunk: dict = {}
    decode: dict = {}
    for engine in getattr(target, "replicas", [target]):
        _record_logits(engine, chunk, decode)
    reqs, wall = _serve(target, p, prompts)
    steps = [decode.get(r.request_id, []) for r in reqs]
    if any(len(d) != p.gen - 1 for d in steps):
        raise SmokeFailure(f"decode steps per request "
                           f"{[len(d) for d in steps]}, expected {p.gen - 1}")
    return Served([r.tokens for r in reqs], _by_request(reqs, chunk),
                  [np.stack(d) for d in steps], wall)


@contextlib.contextmanager
def _attention_cursor_shifted(by: int):
    """Plant a fault for the tolerance's control: while active, paged
    attention traced into a new engine reads every row's cursor ``by``
    positions early, so each query misses its ``by`` latest keys (its own
    included).  KV writes are untouched."""
    mod = importlib.import_module("repro.serving.paged.paged_attention")
    real = mod.paged_attention

    def shifted(q, k, v, block_tables, cursor, **kw):
        return real(q, k, v, block_tables, cursor - by, **kw)

    mod.paged_attention = shifted
    try:
        yield
    finally:
        mod.paged_attention = real


def _serve(target, p: Preset, prompts) -> tuple[list, float]:
    """Submit every prompt greedily and run to completion; returns the
    requests and the wall seconds (compilation included)."""
    sp = SamplingParams(max_new_tokens=p.gen)
    t0 = time.perf_counter()
    reqs = [target.submit(prompts[i], sp) for i in range(len(prompts))]
    target.run()
    wall = time.perf_counter() - t0
    bad = [r.request_id for r in reqs
           if r.status is not Status.FINISHED or len(r.tokens) != p.gen]
    if bad:
        raise SmokeFailure(f"requests {bad} did not finish with {p.gen} "
                           f"tokens")
    return reqs, wall


def _compare(got, want, tol: float, what: str, check: bool = True) -> dict:
    """Rows of ``got`` against ``want`` ([n, V] each) on the stated
    measure; raises SmokeFailure beyond ``tol`` when ``check``."""
    a = np.asarray(got, np.float32)
    b = np.asarray(want, np.float32)
    if a.shape != b.shape:
        raise SmokeFailure(f"{what}: shape {a.shape} vs {b.shape}")
    err = float(np.max(np.abs(a - b)))
    scale = float(np.max(np.abs(b)))
    res = {"max_abs_err": err, "ref_max_abs": scale,
           "rel_err": err / max(scale, 1e-30), "tol": tol,
           "argmax_agree": int(np.sum(a.argmax(-1) == b.argmax(-1))),
           "rows": int(a.shape[0])}
    if check and not (np.isfinite(a).all() and res["rel_err"] <= tol):
        raise SmokeFailure(f"{what}: {res}")
    return res


def _compare_served(got: Served, want: Served, tol: float, what: str,
                    check: bool = True) -> dict:
    """Every logit row the two runs computed from identical inputs: each
    request's chunk step, and each decode step whose input tokens (all the
    request emitted before it) agree.  Where greedy streams part, the top-2
    gap of ``want``'s logits at the parting token, beside that row's
    max |got - want|: argmax can only flip where the gap is within twice
    the error.  With ``check``, at least one decode row must compare."""
    rows_got, rows_want = [got.chunk], [want.chunk]
    n_decode, parts = 0, []
    for i, (tg, tw) in enumerate(zip(got.tokens, want.tokens)):
        same = 0
        while same < len(tg) and tg[same] == tw[same]:
            same += 1
        # decode step t consumes tokens[:t + 1] and emits token t + 1
        k = min(same, len(tg) - 1)
        rows_got.append(got.decode[i][:k])
        rows_want.append(want.decode[i][:k])
        n_decode += k
        if same < len(tg):
            w = want.chunk[i] if same == 0 else want.decode[i][same - 1]
            g = got.chunk[i] if same == 0 else got.decode[i][same - 1]
            top2 = np.sort(w)[-2:]
            parts.append({"token": same, "gap": float(top2[1] - top2[0]),
                          "max_abs_err": float(np.max(np.abs(g - w)))})
    res = _compare(np.concatenate(rows_got), np.concatenate(rows_want), tol,
                   what, check)
    if check and n_decode == 0:
        raise SmokeFailure(f"{what}: no decode step ran on identical inputs")
    res.update(decode_rows=n_decode, parts=parts,
               streams_identical=len(got.tokens) - len(parts))
    return res


def _fmt_served(c: dict) -> str:
    """One ``_compare_served`` result: the rows, the bound, and where the
    greedy streams part."""
    n = c["streams_identical"] + len(c["parts"])
    line = (f"over {c['rows'] - c['decode_rows']} chunk + {c['decode_rows']} "
            f"decode rows: {_fmt(c)}; {c['streams_identical']}/{n} token "
            f"streams identical")
    if c["parts"]:
        line += (", streams part at tokens "
                 + str([q["token"] for q in c["parts"]]) + " where top-2 gap "
                 + str([round(q["gap"], 4) for q in c["parts"]])
                 + " vs max|d| "
                 + str([round(q["max_abs_err"], 4) for q in c["parts"]]))
    return line


def _by_request(reqs, logits: dict) -> np.ndarray:
    missing = [r.request_id for r in reqs if r.request_id not in logits]
    if missing:
        raise SmokeFailure(f"no chunk logits recorded for {missing}")
    return np.stack([np.asarray(logits[r.request_id]) for r in reqs])


def _fmt(c: dict) -> str:
    return (f"max|d|={c['max_abs_err']:.4g} of max|ref|="
            f"{c['ref_max_abs']:.4g} (rel {c['rel_err']:.4g} <= "
            f"{c['tol']}), argmax {c['argmax_agree']}/{c['rows']}")


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _lowered_has_kernel(lowered) -> bool:
    """Whether a lowered program calls a compiled Mosaic kernel (an
    interpreted Pallas kernel lowers to plain HLO instead)."""
    return "tpu_custom_call" in lowered.as_text()


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_dense(p: Preset, seed: int = 0) -> dict:
    """A: dense weights, slot KV, against a non-engine forward."""
    cfg = p.cfg
    args = _serve_args(p, seed, "--kv-layout", "slot")
    zoo, params, _, _ = serve.build_params(cfg, args)
    engine, _ = serve._build_target(cfg, params, args,
                                    max_len=p.prompt_len + p.gen)
    prompts = _prompts(p, seed)
    got = _serve_recorded(engine, p, prompts)
    wall = got.wall
    ref, _ = jax.jit(zoo.prefill)(params, {"tokens": jnp.asarray(prompts)})
    cmp = _compare(got.chunk, ref, LOGIT_TOL, "A engine chunk vs zoo.prefill")
    res = {"requests": len(got.tokens),
           "tokens": sum(len(t) for t in got.tokens),
           "wall_s_with_compile": wall, "steps": engine.stats()["n_steps"],
           "logits": cmp, "peak_bytes_in_use": _peak_bytes()}
    print(f"phase A dense/slot: {res['requests']} requests x "
          f"{p.prompt_len} prompt + {p.gen} greedy, {res['tokens']} tokens "
          f"in {res['steps']} steps, {wall:.1f}s wall incl. compile; "
          f"chunk-step logits vs zoo.prefill: {_fmt(cmp)}; peak "
          f"{res['peak_bytes_in_use']} B", flush=True)
    return res


def phase_sparse(p: Preset, seed: int = 0, expect_kernel: bool = True) -> dict:
    """B: 8:16 + 16:256 compressed weights, paged KV through the Pallas
    kernel, against the same params with slot KV, at the chunk step and at
    every decode step; then the control: the same paged engine with a
    planted one-block attention fault must fail the bound."""
    cfg = p.cfg
    paged_args = _serve_args(p, seed, "--sparse", "--kv-layout", "paged")
    slot_args = _serve_args(p, seed, "--sparse", "--kv-layout", "slot")
    _, params, _, report = serve.build_params(cfg, paged_args)
    prompts = _prompts(p, seed)
    max_len = p.prompt_len + p.gen

    # traced (the trace is never written) for the sparse-linear counter
    traced_args = _serve_args(p, seed, "--sparse", "--kv-layout", "paged",
                              "--trace-out", os.devnull)
    paged, tracer = serve._build_target(cfg, params, traced_args,
                                        max_len=max_len)
    got = _serve_recorded(paged, p, prompts)
    series = tracer.registry.counter(
        "serving_sparse_projections_total").series()
    paths = {path: sum(v for key, v in series.items() if ("path", path) in key)
             for path in ("kernel", "portable")}
    if expect_kernel and not (paths["portable"] == 0 < paths["kernel"]):
        raise SmokeFailure(f"B: sparse projections by path {paths}: not "
                           f"every projection took the kernel")
    a = paged.adapter
    tokens = jnp.zeros((paged.pool.n_slots, 1), jnp.int32)
    kernel = _lowered_has_kernel(a._decode_fn.lower(
        a.params, *a._arena_args(), paged.pool.block_tables,
        paged.pool.pos, tokens))
    if expect_kernel and not kernel:
        raise SmokeFailure("B: the paged decode step does not call the "
                           "compiled Pallas kernel")
    del paged, a

    slot, _ = serve._build_target(cfg, params, slot_args, max_len=max_len)
    want = _serve_recorded(slot, p, prompts)
    del slot
    cmp = _compare_served(got, want, LOGIT_TOL, "B paged kernel vs slot XLA")

    with _attention_cursor_shifted(paged_args.block_size):
        faulty, _ = serve._build_target(cfg, params, paged_args,
                                        max_len=max_len)
        bad = _serve_recorded(faulty, p, prompts)
    del faulty
    control = _compare_served(bad, want, LOGIT_TOL, "B control", check=False)
    if control["rel_err"] <= LOGIT_TOL:
        raise SmokeFailure(f"B control: attention one block early stays "
                           f"within LOGIT_TOL: {control}")

    res = {"dense_bytes": report["dense_bytes"],
           "compressed_bytes": report["compressed_bytes"],
           "ratio": report["ratio"],
           "matrices": report["n_layers_sparsified"],
           "wall_s_with_compile": got.wall, "kernel_compiled": kernel,
           "sparse_projections": paths,
           "logits": cmp, "control": control,
           "token_streams_identical": cmp["streams_identical"],
           "peak_bytes_in_use": _peak_bytes()}
    print(f"phase B 8:16+16:256/paged: {res['matrices']} matrices "
          f"{res['dense_bytes']} -> {res['compressed_bytes']} B "
          f"({res['ratio']:.4f}); paged kernel compiled={kernel}; "
          f"sparse projections by path {paths}; "
          f"{got.wall:.1f}s wall incl. compile; paged vs slot logits "
          f"{_fmt_served(cmp)}; peak {res['peak_bytes_in_use']} B",
          flush=True)
    print(f"phase B control, paged attention one block "
          f"({paged_args.block_size} positions) early: rel "
          f"{control['rel_err']:.4g} > {LOGIT_TOL} (max|d|="
          f"{control['max_abs_err']:.4g} over {control['rows']} rows), "
          f"caught", flush=True)
    return res


def _time_ms(fn, *args, reps: int = 20) -> float:
    """Least mean device time of ``reps`` back-to-back calls over three
    tries, after two warm calls (compilation excluded)."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            y = fn(*args)
        jax.block_until_ready(y)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best * 1e3


def phase_kernels(p: Preset, seed: int = 0,
                  expect_kernel: bool = True) -> dict:
    """C: the sparse linear's kernel branch, compiled, against its
    portable branch, and (``p.timed``) one projection's time on each."""
    scfg = SparsifyConfig(weight_pattern="8:16", outlier_pattern="16:256",
                          scorer="magnitude", use_smoothquant=False)
    nm_only = dataclasses.replace(scfg, outlier_pattern=None)
    kernel, portable = jax.jit(sparse_apply_pallas), jax.jit(
        sparse_apply_portable)
    rows, times = [], []
    for out_dim, in_dim in p.kernel_shapes:
        kw, kx = jax.random.split(jax.random.PRNGKey(seed + 2))
        w = (jax.random.normal(kw, (out_dim, in_dim), jnp.float32)
             / np.sqrt(in_dim)).astype(jnp.bfloat16)
        for quantize in (False, True):
            for fmt, c in (("nm", nm_only), ("nm+outliers", scfg)):
                sw = _to_sparse_weight(w, c, quantize=quantize)
                for b in p.kernel_batches:
                    x = jax.random.normal(jax.random.fold_in(kx, b),
                                          (b, in_dim), jnp.bfloat16)
                    if expect_kernel and not _lowered_has_kernel(
                            kernel.lower(sw, x)):
                        raise SmokeFailure("C: sparse_apply_pallas did not "
                                           "compile to a Mosaic kernel")
                    tag = (f"[{out_dim},{in_dim}] {fmt} "
                           f"{'int8' if quantize else 'bf16'} b={b}")
                    got = kernel(sw, x)
                    if got.shape != (b, out_dim):
                        raise SmokeFailure(f"C: {tag} shape {got.shape}")
                    cmp = _compare(got, portable(sw, x), KERNEL_TOL,
                                   f"C {tag} kernel vs portable")
                    rows.append((tag, cmp))
                    if p.timed and fmt == "nm+outliers" and not quantize:
                        t_k = _time_ms(kernel, sw, x)
                        t_p = _time_ms(portable, sw, x)
                        times.append({"shape": [out_dim, in_dim], "rows": b,
                                      "kernel_ms": t_k, "portable_ms": t_p,
                                      "speedup": t_p / t_k})
    worst = max(c["rel_err"] for _, c in rows)
    print("phase C kernel vs portable: "
          + "; ".join(f"{t} rel {c['rel_err']:.4g}" for t, c in rows)
          + f" (all <= {KERNEL_TOL}, compiled={expect_kernel})", flush=True)
    for t in times:
        print(f"phase C one projection {t['shape']} bf16 8:16+16:256 "
              f"rows={t['rows']}: kernel {t['kernel_ms']:.4f} ms, portable "
              f"one-hot {t['portable_ms']:.4f} ms ({t['speedup']:.2f}x)",
              flush=True)
    return {"cases": {t: c for t, c in rows}, "worst_rel_err": worst,
            "timings": times}


def phase_four_chip(p: Preset, seed: int = 0) -> dict:
    """D: the 1x4 mesh and four one-chip replicas, each against one
    single-device engine in this process, at the chunk step and at every
    decode step on identical inputs."""
    cfg = p.cfg
    prompts = _prompts(p, seed)
    max_len = p.prompt_len + p.gen
    args = _serve_args(p, seed)
    _, params, _, _ = serve.build_params(cfg, args)

    single, _ = serve._build_target(cfg, params, args, max_len=max_len)
    want = _serve_recorded(single, p, prompts)
    del single

    mesh_args = _serve_args(p, seed, "--mesh", "1x4")
    meshed, _ = serve._build_target(cfg, params, mesh_args, max_len=max_len)
    got = _serve_recorded(meshed, p, prompts)
    k_sharding = meshed.pool.k.sharding
    mesh_cmp = _compare_served(got, want, LOGIT_TOL,
                               "D mesh 1x4 vs single device")
    mesh_devices = sorted(d.id for d in k_sharding.device_set)
    del meshed
    print(f"phase D mesh 1x4: KV arena {k_sharding.spec} over devices "
          f"{mesh_devices}, {got.wall:.1f}s wall incl. compile; logits vs "
          f"single device {_fmt_served(mesh_cmp)}", flush=True)

    fleet_args = _serve_args(p, seed, "--replicas", "4")
    fleet, _ = serve._build_target(cfg, params, fleet_args, max_len=max_len)
    got = _serve_recorded(fleet, p, prompts)
    placed = [sorted({d.id for leaf in jax.tree.leaves(e.params)
                      for d in leaf.devices()}) for e in fleet.replicas]
    if len({tuple(d) for d in placed}) != len(placed) \
            or any(len(d) != 1 for d in placed):
        raise SmokeFailure(f"D: replicas are not on distinct single "
                           f"devices: {placed}")
    fleet_cmp = _compare_served(got, want, LOGIT_TOL,
                                "D 4 replicas vs single engine")
    served = [e.stats()["n_finished"] for e in fleet.replicas]
    print(f"phase D 4 replicas: params on devices {placed}, requests per "
          f"replica {served}, {got.wall:.1f}s wall incl. compile; logits "
          f"vs one engine {_fmt_served(fleet_cmp)}", flush=True)
    return {"mesh": mesh_cmp, "mesh_devices": mesh_devices,
            "replica_devices": placed, "replicas": fleet_cmp,
            "requests_per_replica": served}


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the multi-chip phase (needs 4 chips)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    preset = full_preset()
    if args.four_chip:
        if len(devices) < 4:
            print(f"--four-chip needs 4 chips; JAX found {len(devices)}",
                  file=sys.stderr)
            return 2
        phase_four_chip(preset, args.seed)
    else:
        phase_dense(preset, args.seed)
        phase_sparse(preset, args.seed)
        phase_kernels(preset, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
