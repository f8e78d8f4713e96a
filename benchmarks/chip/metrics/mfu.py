"""Device: the model operations of every token the engine processed in
the traced window (prefill rows and decode tokens, attention at the live
context lengths, the head only for rows whose next token is read, at the
active width plan), over the window's length times the chip's peak, in
percent."""

from work import step_flops


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    lo, hi = tr.window
    flops = sum(step_flops(run.model, c.heads, c.ffn, c.contexts,
                           c.logit_rows)
                for c in run.calls if lo <= c.start + run.clock_to_trace < hi)
    if not flops:
        return None
    return 100.0 * flops / (tr.window_s * run.peaks["flops_bf16"])
