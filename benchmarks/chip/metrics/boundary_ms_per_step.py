"""Plan and boundary: host time of the engine's ``engine.boundary`` spans
(``ServingWidthPlanner.select``, ``WidthSwapper.realize_plan`` and any
swap or cache reshape, on the steps that consider a boundary), summed
over the window and divided by the traced ``engine.step`` count.  Needs
the engine's own spans."""

from span_idle import engine_spans, program_spans, steps


def read(run):
    tr = run.trace
    n = steps(tr) if tr is not None and program_spans(tr) else 0
    if not n:
        return None
    return 1e3 * sum(e - s for name, s, e in engine_spans(tr)
                     if name == "engine.boundary") / n
