"""Kernels: the Pallas tiled matmul (``kernels/matmul_tiled.matmul_kernel``,
through ``kernels/ops.matmul``), which runs the dense FFN projections.
The least time the chip needs for the dense-FFN matmuls of the tokens
processed in the traced window (operations and bf16 operand bytes at the
active widths, the larger of the compute and the memory bound per
matmul), over the summed device time of the kernel's calls there and of
the ops that staged their operands in VMEM
(``trace_reduce.kernel_seconds``), in percent.  Decode (a few rows) is
bound by the weights' bytes, a 512-row chunk by its operations.  A
sparse layer's experts run as XLA einsums, not through the kernel, so
its FFN is left out; a model with no dense FFN layer reads nothing."""

from trace_reduce import kernel_seconds
from work import mlp_matmuls, matmul_bytes, matmul_flops, roofline_seconds

# The trace names a Pallas call by its HLO instruction, with no kernel
# name; the tiled matmul is the only Pallas kernel on the chunked serving
# path (chunked prefill attends in plain jnp), so its calls are the ops
# whose target is the TPU custom call.
KERNEL_OP = 'custom_call_target="tpu_custom_call"'


def read(run):
    tr = run.trace
    if tr is None:
        return None
    dev = kernel_seconds(tr, KERNEL_OP)
    if dev <= 0:
        return None
    lo, hi = tr.window
    least = 0.0
    for c in run.calls:
        if not lo <= c.start + run.clock_to_trace < hi:
            continue
        m = len(c.contexts)
        for i, f in enumerate(c.ffn):
            if run.model.sparse(i):
                continue
            for mm in mlp_matmuls(m, run.model.d_model, int(f),
                                  run.model.gated):
                least += roofline_seconds(matmul_flops(*mm),
                                          matmul_bytes(*mm), run.peaks)[0]
    return 100.0 * least / dev if least > 0 else None
