#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
and a traffic mix (``bench/registry.py``).  The run, in one process that
holds the chip:

  1. makes the weights on the device from ``--seed`` (and compresses them
     as the configuration states), and builds the ServingEngine;
  2. runs every chunk-program shape the mix can reach, the decode program
     and the sampler once (set-up, from the compile cache after the first
     run in a checkout);
  3. offers the mix's traffic for its ``warmup_s`` to reach steady state,
     then measures a window of ``--seconds``;
  4. checks what the window served against the plain reference, after the
     program's state is freed;
  5. prints, last on standard output, one JSON line: the cell's end-to-end
     metrics (``--trace 0``) or its per-layer metrics read from a profiled
     stretch of the window (``--trace 1``), and the device it ran on.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.  JAX's compile cache lives in ``.jax_cache/``
of the checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says.
``--rehearse`` runs the same code on the CPU at the configuration's
``smoke`` sizes and prints no metric.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


class NoChip(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU at the smoke sizes; prints no metric")
    return ap.parse_args(argv)


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def setup_jax(cache: bool = True):
    import jax
    if not cache:
        jax.config.update("jax_enable_compilation_cache", False)
        return jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    # keep the small programs too, so a later run loads every one of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def device_info(jax) -> dict:
    d = jax.devices()[0]
    stats = d.memory_stats() or {}
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": stats.get("peak_bytes_in_use")}


def load_cell(name: str, rehearse: bool):
    """(workload, configuration, mix, peaks) of a cell; on the chip unless
    ``rehearse``, which takes the configuration's and mix's smoke sizes.
    Raises NoChip where JAX finds no TPU or too few chips."""
    import jax
    from bench import registry
    wl = registry.workload(name)
    conf = registry.config(wl["config"])
    mix = registry.traffic(wl["traffic"])
    if rehearse:
        return (wl, merged(conf, conf.get("smoke", {})),
                merged(mix, mix.get("smoke", {})), None)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < wl["chips"]:
        raise NoChip(f"{wl['name']} needs {wl['chips']} chips; JAX found "
                     f"{len(devices)}")
    return wl, conf, mix, registry.peaks(devices[0].device_kind)


def serve(wl: dict, conf: dict, mix: dict, seed: int, seconds: float,
          trace: bool, peaks: dict | None, t_start: float = T_START) -> dict:
    """Set-up and the window of one run: {"window", "setup_s", "device",
    "e2e", "engine", ...}, and with ``trace`` "per_layer", "busy_s",
    "window_s", "breakdown".  ``t_start`` is when set-up began."""
    import jax
    from bench import check, harness, measures, registry

    counter = harness.CompileCounter()
    engine, params = harness.build(conf, seed)
    weight_bytes = check.tree_bytes(engine.adapter.params)
    calls = harness.CallLog(engine)
    dep = conf["deployment"]
    shapes = harness.chunk_shapes(dep, mix["prompt"]["min"])
    harness.warm(engine, shapes)
    calls.calls.clear()
    source = registry.generator(mix["generator"]).make(mix, seed,
                                                       dep["n_slots"])
    tdir = tr = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        span = min(mix["trace_s"], seconds)
        tr = ((seconds - span) / 2, span, tdir)
    window = harness.drive(engine, source, vocab=conf["model"]["vocab"],
                           seed=seed, warmup_s=mix["warmup_s"],
                           seconds=seconds, counter=counter, calls=calls,
                           trace=tr)
    device = device_info(jax)
    out = {"window": window, "setup_s": window.start - t_start,
           "device": device, "engine": engine, "params": params,
           "calls": calls, "programs_warmed": len(shapes) + 1,
           "weight_bytes": weight_bytes}
    out["e2e"] = measures.end_to_end(window)
    out["e2e"]["setup_s"] = out["setup_s"]
    if trace:
        from bench import trace_reduce
        _, per_layer = registry.metrics_for(wl["name"])
        readers = {m["name"]: registry.metric_reader(m["name"])
                   for m in per_layer}
        kernels = tuple(sorted({r.KERNEL for r in readers.values()
                                if hasattr(r, "KERNEL")}))
        traced = [c for c in calls.calls if c.traced]
        red = trace_reduce.reduce(
            trace_reduce.load(tdir), traced,
            f"jit_{engine.adapter._step_fn.__name__}", kernels)
        ctx = trace_reduce.Context(conf=conf, peaks=peaks, window=window,
                                   reduced=red, device=device)
        out["per_layer"] = {}
        for mdef in per_layer:
            v = readers[mdef["name"]].read(ctx)
            if v is not None:
                out["per_layer"][mdef["name"]] = (v, mdef["unit"])
        out["busy_s"], out["window_s"] = red.busy_s, red.window_s
        out["breakdown"] = red.breakdown()
        shutil.rmtree(tdir, ignore_errors=True)
    return out


def check_served(res: dict, conf: dict, seed: int,
                 control: bool = False) -> dict:
    """Frees the program's state, then compares a sample of what the window
    served with the plain reference of ``conf``: res gains "reading",
    "correct", "checked", "attempted", "failed", and with ``control`` the
    control's "control_correct" and "control_checked"."""
    from bench import check, measures
    t0 = time.monotonic()
    window = res["window"]
    recs = check.sample(window.records, seed, conf["check"]["requests"])
    served = [(r.spec, list(r.req.tokens)) for r in recs]
    for k in ("engine", "params", "calls"):
        res.pop(k, None)
    for r in window.records:
        r.req = None
    gc.collect()
    reading = check.widest_gaps(conf, seed, served, control=control)
    reading["weight_bytes"] = res["weight_bytes"]
    res["reading"] = reading
    res["correct"], res["checked"] = check.result(reading, conf)
    if control:
        res["control_correct"], res["control_checked"] = check.result(
            reading["control"], conf)
    due = measures.due_in_window(window)
    res["attempted"] = len(due)
    res["failed"] = sum(r.rejected for r in due)
    res["check_s"] = time.monotonic() - t0
    return res


def result_line(wl: dict, res: dict, trace: bool, rehearse: bool) -> dict:
    """The last line of a run: correct, counts, metrics, device, and the
    numbers compared with their limits (last)."""
    from bench import registry
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if rehearse:
        line["rehearsal"] = True
    else:
        if trace:
            metrics = {k: {"value": v, "unit": u}
                       for k, (v, u) in res["per_layer"].items()}
        else:
            e2e, _ = registry.metrics_for(wl["name"])
            metrics = {m["name"]: {"value": res["e2e"][m["name"]],
                                   "unit": m["unit"]} for m in e2e}
        line["metrics"] = metrics
        dev = dict(res["device"])
        if trace:
            dev.update(busy_s=res["busy_s"], window_s=res["window_s"])
            line["breakdown"] = res["breakdown"]
        line["device"] = dev
    line["checked"] = res["checked"]
    return line


def info_line(res: dict) -> dict:
    from bench import measures
    w = res["window"]
    return {"window": {
        "seconds": w.end - w.start, "setup_s": res["setup_s"],
        "compiles_in_window": w.compiles,
        "programs_warmed": res["programs_warmed"],
        **measures.load(w), **measures.generator_lateness(w),
        **{k: v for k, v in res["e2e"].items() if k != "setup_s"}},
        "reading": res.get("reading"), "check_s": res.get("check_s")}


def main(argv=None) -> int:
    args = parse(argv)
    setup_jax(cache=not args.rehearse)
    try:
        wl, conf, mix, peaks = load_cell(args.workload, args.rehearse)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    res = serve(wl, conf, mix, args.seed, args.seconds, bool(args.trace),
                peaks)
    check_served(res, conf, args.seed)
    print(json.dumps(info_line(res)), flush=True)
    for name, c in res["checked"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"check correct: {res['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result_line(wl, res, bool(args.trace), args.rehearse)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
