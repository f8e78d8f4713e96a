"""Engine step loop, host-device transfers: device-idle time inside the
engine's ``engine.inputs`` (``jnp.asarray`` of the last tokens and the
positions, host to device) and ``engine.sync`` (``np.asarray`` of the
sampled tokens: the wait for the device and the copy back) spans, over
the traced ``engine.step`` count.  Gaps are cut at span boundaries
(``span_idle``).  Needs the engine's own spans."""

from span_idle import TRANSFERS, idle_by_innermost, program_spans, steps


def read(run):
    tr = run.trace
    n = steps(tr) if tr is not None and program_spans(tr) else 0
    if not n:
        return None
    idle = idle_by_innermost(tr)
    return 1e3 * sum(idle.get(name, 0.0) for name in TRANSFERS) / n
