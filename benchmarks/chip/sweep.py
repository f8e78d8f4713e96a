#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: one process, one engine, a few
offered rates, one window each.

    python3 benchmarks/chip/sweep.py --workload qwen1.5-0.5b.chat \\
        --seed 1 --seconds 30 --rates 1,2,4,6

The knee is the highest rate at which the queue of requests not yet
seated does not grow over the window and nothing fails.  Each rate prints
one JSON line: the end-to-end metrics, the requests waiting at the start
and the end of the window (means over its first and last quarter), and
the slope of a line through the waiting count in requests per second.
The rate found is written into the mix file by hand; the benchmark's runs
never search for one.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench  # noqa: E402

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    bench.init_jax()
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    try:
        sess = bench.Session(args.workload, args.seed, args.seconds,
                             rehearse=args.rehearse, log=log, rates=rates)
    except bench.NoChip as e:
        log(f"sweep.py: {e}")
        return 2
    if sess.schedule.mode != "open":
        log("sweep.py: a closed-loop cell has no offered rate")
        return 2
    for r in rates:
        sess.ad.waiting_log.clear()
        w = bench.window(sess, sess.at_rate[r], args.seconds, False)
        run = bench.RunRecord(
            mode="open", window=(w.t_w, w.t_end),
            requests=list(w.recs.values()), steps=w.steps, calls=[],
            compiles=[c for c in sess.compiles.events
                      if w.t_w <= c[0] < w.t_end],
            model=sess.model, peaks=sess.peaks)
        e2e = bench.end_to_end(run, args.seconds)
        t = np.array([x[0] - w.t_w for x in w.waiting])
        q = np.array([x[1] for x in w.waiting], np.float64)
        quarter = args.seconds / 4
        due = [w.recs[i] for i in w.in_window]
        out = {"rate_rps": r, **e2e,
               "requests": len(due),
               "failed": sum(not x.ok for x in due),
               "waiting_first_quarter": float(q[t < quarter].mean())
               if (t < quarter).any() else None,
               "waiting_last_quarter": float(q[t >= 3 * quarter].mean())
               if (t >= 3 * quarter).any() else None,
               "waiting_slope_per_s": float(np.polyfit(t, q, 1)[0])
               if len(t) > 2 else None,
               "compiles_in_window": len(run.compiles),
               "steps": len(w.steps)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
