"""From a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

``reduce_trace`` reads the trace JAX's profiler wrote for the traced part
of the window and returns a ``Reduced``:

* device-busy intervals: the union of the operations' intervals on each
  device plane (``/device:TPU:<n>``, line ``XLA Ops``), clipped to the
  window, and ``busy_s``, their length averaged over the devices;
* per-operation device self time (line ``XLA Ops``; a control-flow op
  such as the ``while`` of a layer scan contains the ops of its body, so
  each op is charged its duration less that of the ops nested in it), and
  the module (executable) executions in order (line ``XLA Modules``,
  named ``<module>(<program id>)``);
* idle gaps, each attributed to the innermost host span the benchmark
  opened around the engine (``engine.step``, ``engine.decode``, ...)
  that covers the gap's middle.

Trace times are seconds from the trace's own origin.  The window's host
span (``bench.window``) anchors the host clock: ``offset`` turns a host
clock reading into trace seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("engine.", "bench.")
NO_SPAN = "(no engine span)"


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]          # trace seconds
    offset: float                        # trace s = host clock + offset
    busy: List[Tuple[float, float]]      # merged, first device, in window
    busy_s: float                        # busy seconds, mean over devices
    ops: Dict[str, float]                # op name -> device self seconds
    modules: List[Tuple[str, float, float]]   # (name, start, end) in order
    spans: List[Tuple[str, float, float]]     # host spans in the window
    n_devices: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_within(self, a: float, b: float) -> float:
        """Device-busy seconds inside [a, b] (trace seconds)."""
        i = max(bisect.bisect_right(self.busy, (a, float("inf"))) - 1, 0)
        total = 0.0
        for s, e in self.busy[i:]:
            if s >= b:
                break
            total += max(0.0, min(e, b) - max(s, a))
        return total

    def gaps(self) -> List[Tuple[float, float]]:
        out, t = [], self.window[0]
        for s, e in self.busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return out

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds by the innermost engine span over each gap."""
        spans = sorted(self.spans, key=lambda s: s[1])
        starts = [s[1] for s in spans]
        out: Dict[str, float] = defaultdict(float)
        for a, b in self.gaps():
            mid = 0.5 * (a + b)
            best = None
            for name, s, e in spans[:bisect.bisect_right(starts, mid)]:
                if s <= mid <= e and name != "bench.window" and (
                        best is None or e - s < best[2] - best[1]):
                    best = (name, s, e)
            out[best[0] if best else NO_SPAN] += b - a
        return dict(out)

    def breakdown(self) -> dict:
        ops = defaultdict(float)
        for k, v in self.ops.items():
            ops[short_op(k)] += v
        ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle[:10]]}


def self_times(events) -> Dict[str, float]:
    """Op name -> device seconds not covered by ops nested inside it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[str, float]] = []          # (name, end) of open ops
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        out[name] += e - s
        if stack:                     # the parent's own time drops by it
            out[stack[-1][0]] -= e - s
        stack.append((name, e))
    return dict(out)


def short_op(name: str) -> str:
    """``%copy.81 = bf16[1,16,2048]{...} copy(...)`` -> ``copy.81
    bf16[1,16,2048] copy``; a Pallas call keeps its target."""
    lhs, _, rhs = name.partition(" = ")
    shape = "tuple" if rhs.startswith("(") \
        else rhs.split("{", 1)[0].split(" ", 1)[0]
    kind = re.search(r"\b([a-z][a-z0-9_.-]*)\(", rhs)
    out = f"{lhs.lstrip('%')} {shape} {kind.group(1) if kind else ''}"
    out = out.strip()
    if 'custom_call_target="tpu_custom_call"' in name:
        out += " tpu_custom_call"
    return out


def module_base(name: str) -> str:
    """``jit_counted(1578...)`` -> ``jit_counted``."""
    return name.split("(", 1)[0]


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def reduce_trace(trace_dir: str, anchor: str, anchor_host_t: float,
                 window_s: float) -> Optional[Reduced]:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_file(path, anchor, anchor_host_t, window_s)


def reduce_file(path: str, anchor: str, anchor_host_t: float,
                window_s: float) -> Optional[Reduced]:
    """Reduce one ``.xplane.pb``.  ``anchor`` names the host span that
    opened at host clock ``anchor_host_t``; the window is ``window_s``
    from there.  None when the trace holds no device operation."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans, a0 = [], None
    dev_ops: List[List[Tuple[str, float, float]]] = []
    dev_mods: List[List[Tuple[str, float, float]]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line))
                elif line.name == MODULES_LINE:
                    mods.extend(_events(line))
            if ops:
                dev_ops.append(ops)
                dev_mods.append(mods)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.startswith(SPAN_PREFIXES):
                        spans.append((name, s, e))
                        if name == anchor and a0 is None:
                            a0 = s
    if a0 is None or not dev_ops:
        return None
    w = (a0, a0 + window_s)
    busy_each = [merge(clip([(s, e) for _, s, e in ops], *w))
                 for ops in dev_ops]
    busy_s = sum(sum(e - s for s, e in b) for b in busy_each) / len(busy_each)
    op_time = self_times([(n, s, e) for n, s, e in dev_ops[0]
                          if w[0] <= s < w[1]])
    mods = sorted(((n, s, e) for n, s, e in dev_mods[0]
                   if w[0] <= s < w[1]), key=lambda m: m[1])
    return Reduced(window=w, offset=a0 - anchor_host_t, busy=busy_each[0],
                   busy_s=busy_s, ops=dict(op_time), modules=mods,
                   spans=[sp for sp in spans if sp[2] > w[0] and sp[1] < w[1]],
                   n_devices=len(dev_ops))


def call_device_times(red: Reduced, calls, offset: float, modules: set,
                      slack: float = 2e-3) -> list:
    """Pair each recorded engine call (host clock) that lies in the traced
    window with the execution of its serving executable on the device:
    the device runs executables in the order the host dispatched them, so
    the k-th call takes the first later execution of a serving module.
    Returns [(call, device seconds)]."""
    mods = [m for m in red.modules if module_base(m[0]) in modules]
    out, j = [], 0
    for c in sorted(calls, key=lambda c: c.start):
        t = c.start + offset
        if not red.window[0] <= t < red.window[1]:
            continue
        while j < len(mods) and mods[j][1] < t - slack:
            j += 1
        if j == len(mods):
            break
        out.append((c, mods[j][2] - mods[j][1]))
        j += 1
    return out


OPERAND = re.compile(r"(\w+\[[^\]]*\]\{[^}]*\})\s+(%[\w.\-]+)")


def kernel_seconds(red: Reduced, needle: str) -> float:
    """Device seconds of the kernel calls (ops whose name holds
    ``needle``) and of the ops that placed their operands in the core's
    fast memory.  The compiler may stage an operand in VMEM (memory space
    ``S(1)`` in its layout) with an op of its own before the call; the
    call then reads it at no HBM cost, and its own time leaves out the
    transfer its work needs.  That staging op is charged to the kernel."""
    kernels = [k for k in red.ops if needle in k]
    total = sum(red.ops[k] for k in kernels)
    staged = set()
    for k in kernels:
        args = k.split("custom-call(", 1)[-1]
        for shape, name in OPERAND.findall(args):
            if "S(1)" in shape:
                staged.add(f"{name} = {shape}")
    return total + sum(v for n, v in red.ops.items()
                       if staged and n.startswith(tuple(staged)))
