"""Time to first token at the highest percentile that keeps ten of the
window's requests beyond it (95th at 200 or more requests), from the due
time until the host holds the token."""

import numpy as np


def read(run):
    due = run.window_requests()
    ttft = [r.token_t[0] - r.due for r in due if r.token_t]
    if len(ttft) < 20:
        return None
    q = min(95.0, 100.0 * (1.0 - 10.0 / len(ttft)))
    return float(np.percentile(ttft, q)) * 1e3
