"""Device milliseconds per round of the compiled round program: its module
executions in the trace (``jit_round_fn`` of the batched engine,
``jit_round_body`` of the sharded one), averaged over the chips."""

from bench import trace_reduce

NAMES = ("jit_round_fn", "jit_round_body")


def read(ctx):
    s = trace_reduce.op_seconds(ctx["trace"], lambda n: n.startswith(NAMES),
                                ctx["lo"], ctx["hi"], line="modules")
    return s * 1e3 / ctx["rounds"] if s else None
