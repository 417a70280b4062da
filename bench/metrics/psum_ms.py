"""Device milliseconds per round in all-reduce operations (the sharded
engine's aggregation psum over the client mesh), averaged over the
chips."""

from bench import trace_reduce


def read(ctx):
    s = trace_reduce.op_seconds(ctx["trace"], lambda n: "all-reduce" in n,
                                ctx["lo"], ctx["hi"])
    return s * 1e3 / ctx["rounds"] if s else None
