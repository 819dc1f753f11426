#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds <s> [--warmup <s>] [--control] [--int8]

In one process, for each seed: the cell's set-up and window as
``bench/run.py`` runs them (the same engine, programs, batch and traffic),
then the comparison with the plain reference.  With ``--control`` the same
sequences are also read with the reference computed in float8 (the
precision below the configuration's bfloat16): the gap of the token that
float8 puts first, judged by the same limits.  With ``--int8`` each seed is
also served by the program with its own int8 N:M values (weight-only
quantization) and judged against the cell's stated configuration.  One
JSON line per seed and variant: the verdicts, the numbers compared, and
the window's load.  ``--warmup`` shortens the traffic's warm-up, so that a
seed costs less; the window must still finish requests.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run  # bench/run.py, beside this file


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--warmup", type=float)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--int8", action="store_true",
                    help="also serve each seed with the program's own int8 "
                         "N:M values and judge it the same way")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    run.setup_jax(cache=not args.rehearse)
    try:
        wl, conf, mix, peaks = run.load_cell(args.workload, args.rehearse)
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if args.warmup is not None:
        mix = dict(mix, warmup_s=args.warmup)
    variants = [("program", conf, args.control)]
    if args.int8:
        variants.append(("program_int8", run.merged(
            conf, {"weights": {"values": "int8"}}), False))
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, served, control in variants:
            res = run.serve(wl, served, mix, seed, args.seconds, False,
                            peaks, t_start=time.monotonic())
            run.check_served(res, conf, seed, control=control)
            out = {"seed": seed, "variant": name, "correct": res["correct"],
                   "checked": res["checked"]}
            if control:
                out["control_correct"] = res["control_correct"]
                out["control_checked"] = res["control_checked"]
            print(json.dumps({**out, **run.info_line(res)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
