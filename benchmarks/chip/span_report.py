#!/usr/bin/env python3
"""A traced run of one cell with the engine's own spans: where the
device's idle time goes, phase by phase.

    python3 benchmarks/chip/span_report.py --workload qwen1.5-0.5b.chat \\
        --seed 7 --seconds 30

The run is ``run.py --trace 1``'s, except that the engine opens its own
spans (``ContinuousServeEngine.spans``, ``repro.serving.spans``) in place
of the five that ``engine_adapter.EngineAdapter.tap`` opens around it;
the adapter still records each model call's work.  The last line of
standard output is one JSON object: ``correct``, the cell's per-layer
metrics, the metrics that read the engine's own spans
(``idle_sync_ms_per_step``, ``idle_sched_ms_per_step``,
``boundary_ms_per_step``), the window's idle seconds by innermost span
with gaps cut at span boundaries (``idle_cut_s``) and by the midpoint
rule of ``breakdown`` (``idle_mid_s``), the traced step count, the span
names with their counts, and ``device`` and ``info`` as ``run.py`` gives
them.  Off a TPU it exits 2; ``--rehearse`` runs on the CPU at a reduced
size, where the trace holds no device and no line is printed.  The
benchmark itself does not run this script.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench  # noqa: E402  (records the process start for setup_s)

ENGINE_SPAN_METRICS = ("idle_sync_ms_per_step", "idle_sched_ms_per_step",
                       "boundary_ms_per_step")


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def own_spans(adapter_cls) -> None:
    """Make ``tap`` record the model calls and switch the engine's own
    spans on, instead of opening spans of its own."""
    tap = adapter_cls.tap

    def tap_own(self, spans: bool) -> None:
        tap(self, False)
        self.eng.spans = spans
    adapter_cls.tap = tap_own


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    bench.init_jax()
    import engine_adapter
    import trace_reduce
    from span_idle import idle_by_innermost, steps
    own_spans(engine_adapter.EngineAdapter)
    kept = {}
    reduce = trace_reduce.reduce_trace

    def keep(*a, **kw):
        kept["red"] = reduce(*a, **kw)
        return kept["red"]
    trace_reduce.reduce_trace = keep
    try:
        res = bench.run_cell(args.workload, args.seed, args.seconds, True,
                             rehearse=args.rehearse, log=_log)
    except bench.NoChip as e:
        _log(f"span_report.py: {e}")
        return 2
    red = kept.get("red")
    out = {"correct": res["correct"], "metrics": res["metrics"]}
    if red is not None:
        run = SimpleNamespace(trace=red)
        for name in ENGINE_SPAN_METRICS:
            v = bench.load_reader(name)(run)
            if v is not None:
                out["metrics"][name] = {"value": v, "unit": "ms"}
        cut = idle_by_innermost(red)
        out["idle_cut_s"] = dict(sorted(cut.items(), key=lambda kv: -kv[1]))
        mid = red.idle_by_span()
        out["idle_mid_s"] = dict(sorted(mid.items(), key=lambda kv: -kv[1]))
        out["steps"] = steps(red)
        out["spans"] = dict(Counter(n for n, _, _ in red.spans))
    out["device"] = res["device"]
    out["info"] = res["info"]
    if args.rehearse:
        _log("rehearsal: " + json.dumps(out))
        return 0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
