"""Device-idle time cut at the engine's span boundaries.

``trace_reduce.Reduced.idle_by_span`` charges a whole idle gap to the
innermost span over its middle.  Here each gap is cut at every boundary
of an engine span inside it, and every piece is charged to the innermost
engine span over it: the latest-opened of the spans open there, since
spans of one thread nest.  Pieces under no ``engine.step`` are charged to
``trace_reduce.NO_SPAN``.

The engine's own spans (``serving.spans``, switched on by the engine's
``spans`` attribute) name every phase of a step; ``program_spans`` tells
them from the five that the benchmark's adapter opens around the engine.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Tuple

from trace_reduce import NO_SPAN

STEP = "engine.step"
# spans that wrap one model call each, and the two host-device transfers
MODEL_CALLS = ("engine.decode", "engine.chunk", "engine.sample",
               "engine.write_slot")
TRANSFERS = ("engine.inputs", "engine.sync")
# opened only by the engine itself, once in every step that does work
PROGRAM_MARK = "engine.retire"


def engine_spans(red) -> List[Tuple[str, float, float]]:
    """The engine's spans in the window, clipped to it."""
    lo, hi = red.window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in red.spans
            if n.startswith("engine.") and e > lo and s < hi]


def program_spans(red) -> bool:
    """Whether the trace holds the engine's own spans."""
    return any(n == PROGRAM_MARK for n, _, _ in red.spans)


def steps(red) -> int:
    """``engine.step`` spans that overlap the window."""
    return sum(n == STEP for n, _, _ in engine_spans(red))


def idle_by_innermost(red) -> Dict[str, float]:
    """Idle seconds of the window by the innermost engine span over each
    piece of each gap (``NO_SPAN`` where no ``engine.step`` is open)."""
    lo, hi = red.window
    spans = engine_spans(red)
    marks = sorted([(s, 1, k) for k, (_, s, _) in enumerate(spans)]
                   + [(e, 0, k) for k, (_, _, e) in enumerate(spans)]
                   + [(hi, 0, -1)])        # ends before starts at a tie
    out: Dict[str, float] = defaultdict(float)
    active: Dict[int, Tuple[str, float, float]] = {}
    t = lo
    for at, opens, k in marks:
        if at > t:
            idle = (at - t) - red.busy_within(t, at)
            if idle > 0:
                out[_charge(active)] += idle
            t = at
        if k < 0:
            continue
        if opens:
            active[k] = spans[k]
        else:
            active.pop(k, None)
    return dict(out)


def _charge(active) -> str:
    if not any(n == STEP for n, _, _ in active.values()):
        return NO_SPAN
    return max(active.values(), key=lambda sp: (sp[1], -sp[2]))[0]


def step_contents(red) -> List[Tuple[float, set]]:
    """Each ``engine.step`` span inside the window: its duration and the
    names of the spans that open inside it."""
    lo, hi = red.window
    spans = sorted((s, n) for n, s, _ in red.spans if n != STEP)
    starts = [s for s, _ in spans]
    out = []
    for n, s, e in red.spans:
        if n != STEP or s < lo or e > hi:
            continue
        i, j = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        out.append((e - s, {name for _, name in spans[i:j]}))
    return out
