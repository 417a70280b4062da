"""Roofline share of the masked-matmul kernel pair: the least time its
required work could take on the chip, max(ops / peak FLOP/s, bytes / peak
bytes/s) over the alive blocks only (``flops.RoundCost``), over the kernel
events' summed device time (forward and both backward calls).

The trace names the kernel calls after the jitted wrappers where one call
serves a whole cohort with shared block flags (``%jvp_jit_masked_matmul__``,
``%transpose_jvp_jit_masked_matmul___``, ``..._dk___``: the capable
clients), and ``%closed_call`` where XLA loops the call over clients with
flags of their own (the stragglers; the event is the kernel, or the kernel
fused with the write of its output row)."""

from bench import trace_reduce


def _is_kernel(op: str) -> bool:
    name = op.split(" = ", 1)[0]
    return "masked_matmul" in name or name.startswith("%closed_call")


def read(ctx):
    s = trace_reduce.op_seconds(ctx["trace"], _is_kernel, ctx["lo"],
                                ctx["hi"])
    cost, pk = ctx["cost"], ctx["peaks"]
    if not s or not cost.kernel_ops:
        return None
    least = max(cost.kernel_ops / pk["bf16_flops_per_s"],
                cost.kernel_bytes / pk["hbm_bytes_per_s"])
    return 100.0 * least * ctx["rounds"] / ctx["chips"] / s
