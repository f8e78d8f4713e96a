"""Engine step loop: host time per engine step, each ``engine.step`` span
less the device-busy time inside it, averaged over the traced steps."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    steps = [(s, e) for n, s, e in tr.spans if n == "engine.step"
             and tr.window[0] <= s and e <= tr.window[1]]
    if not steps:
        return None
    host = [(e - s) - tr.busy_within(s, e) for s, e in steps]
    return 1e3 * sum(host) / len(host)
