"""Model step: device time per call of the prefill-chunk executable, the
mean over the chunk calls in the traced window."""

from trace_reduce import call_device_times


def read(run):
    if run.trace is None:
        return None
    pairs = call_device_times(run.trace, run.calls, run.clock_to_trace,
                              run.exec_modules)
    dev = [s for c, s in pairs if c.kind == "chunk"]
    return 1e3 * sum(dev) / len(dev) if dev else None
