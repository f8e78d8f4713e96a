"""Model step, chunked prefill beside decode: the mean ``engine.step``
duration of the steps that hold at least one ``engine.chunk`` span, less
that of the decode steps (holding ``engine.decode``) that hold none:
what a join's prompt chunks add to the step that the decoding requests
wait on."""

from span_idle import step_contents


def read(run):
    tr = run.trace
    if tr is None:
        return None
    steps = step_contents(tr)
    chunk = [d for d, held in steps if "engine.chunk" in held]
    plain = [d for d, held in steps
             if "engine.decode" in held and "engine.chunk" not in held]
    if not chunk or not plain:
        return None
    return 1e3 * (sum(chunk) / len(chunk) - sum(plain) / len(plain))
