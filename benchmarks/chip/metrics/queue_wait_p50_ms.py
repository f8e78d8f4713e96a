"""Queue and admission: median wait from a request's due time until the
engine seated it in a slot, over the requests due in the window."""

import numpy as np


def read(run):
    waits = [r.seated_t - r.due for r in run.window_requests()
             if r.seated_t is not None]
    return float(np.percentile(waits, 50)) * 1e3 if waits else None
