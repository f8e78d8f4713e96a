"""Engine step loop, host scheduling: device-idle time inside
``engine.step`` but outside every span of a model call (``engine.decode``,
``engine.chunk``, ``engine.sample``, ``engine.write_slot``) and of the two
transfers (``engine.inputs``, ``engine.sync``), over the traced
``engine.step`` count: delivery, boundary checks, admission, prompt
assembly, commits, retiring tokens.  Gaps are cut at span boundaries
(``span_idle``).  Needs the engine's own spans."""

from trace_reduce import NO_SPAN
from span_idle import (MODEL_CALLS, TRANSFERS, idle_by_innermost,
                       program_spans, steps)


def read(run):
    tr = run.trace
    n = steps(tr) if tr is not None and program_spans(tr) else 0
    if not n:
        return None
    skip = set(MODEL_CALLS) | set(TRANSFERS) | {NO_SPAN}
    idle = idle_by_innermost(tr)
    return 1e3 * sum(v for name, v in idle.items() if name not in skip) / n
