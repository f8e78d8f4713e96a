#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's widest logit gap on
many seeds, and the control's on a few, in one process.

    python3 benchmarks/chip/calibrate.py --workload qwen1.5-0.5b.chat \\
        --seconds 30 --seeds 11,12,13,14 --control 3

Each seed builds the engine anew with its own weights and runs one window
of the cell at its own load, then the check.  The control is the plain
reference computed in fp8 (e4m3, one scale per tensor) put in the
program's place: at each position of the same prompts and served tokens,
the gap of the token it puts first, read on the float32 logits.  The
control runs on the first ``--control`` seeds.  One JSON line per seed.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    bench.init_jax()
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    for i, seed in enumerate(seeds):
        try:
            sess = bench.Session(args.workload, seed, args.seconds,
                                 rehearse=args.rehearse, log=log)
        except bench.NoChip as e:
            log(f"calibrate.py: {e}")
            return 2
        w = bench.window(sess, sess.schedule, args.seconds, False)
        served = {rid: sess.ad.served_tokens(rid)
                  for rid, r in w.recs.items() if r.ok}
        sess.free_engine()
        limit = float(sess.conf["limits"]["logit_gap"])
        chk = bench.check(sess, w.recs, served, seed, limit,
                          control=i < args.control)
        print(json.dumps({"seed": seed, **chk}), flush=True)
        del sess, w, served
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
