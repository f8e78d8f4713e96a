#!/usr/bin/env python3
"""Chip benchmark of the continuous serving path: one run of one cell.

    python3 benchmarks/chip/run.py --workload qwen1.5-0.5b.chat \\
        --seed 7 --seconds 30 --trace 0

Reads the cell from ``BENCHMARK.json`` at the root of the checkout, its
configuration from ``benchmarks/chip/configs/`` and its traffic mix from
``benchmarks/chip/traffic/``; serves on the device JAX finds; prints, as
the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``
(with ``--trace 1`` also ``busy_s`` and ``window_s``), ``breakdown``
(``--trace 1``) and, last, ``checks``: each number compared with its
limit.  The same comparisons end standard error.

Off a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.  ``--rehearse`` runs the whole path on the CPU at a
reduced size (``JAX_PLATFORMS=cpu``) and never prints the result line.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench  # noqa: E402  (records the process start for setup_s)


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a reduced size; no result line")
    args = ap.parse_args(argv)

    bench.init_jax()
    try:
        res = bench.run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), rehearse=args.rehearse,
                             log=_log)
    except bench.NoChip as e:
        _log(f"run.py: {e}")
        return 2
    info = res.pop("info", None)
    if info is not None:
        _log("info: " + json.dumps(info))
    for name, c in res["checks"].items():
        _log(f"check {name}: {c['value']} (limit {c['limit']})")
    if args.rehearse:
        _log("rehearsal: " + json.dumps(res))
        return 0
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
