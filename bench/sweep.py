#!/usr/bin/env python3
"""Offered rate against sustained rate, for an open-loop cell.

    python3 bench/sweep.py --workload <cell> --rates 0.2,0.3,0.4 \
        --seconds <s> --seed <n>

In one process, for each rate: the cell's set-up and window as
``bench/run.py`` runs them, with the mix's ``rate_per_s`` replaced.  One
JSON line per rate: requests due, refused and finished in the window,
the queue at the window's start and end, the batch, and the end-to-end
metrics.  The knee is the highest rate whose queue does not grow across
the window and which refuses nothing; a cell's mix offers a rate below it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run  # bench/run.py, beside this file


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    run.setup_jax(cache=not args.rehearse)
    try:
        wl, conf, mix, peaks = run.load_cell(args.workload, args.rehearse)
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for rate in (float(r) for r in args.rates.split(",")):
        t = time.monotonic()
        res = run.serve(wl, conf, dict(mix, rate_per_s=rate), args.seed,
                        args.seconds, False, peaks, t_start=t)
        print(json.dumps({"rate_per_s": rate, **run.info_line(res)}),
              flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
