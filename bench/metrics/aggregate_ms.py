"""Device milliseconds per round in aggregation (Eq. 10 and the update's
codec, with the sharded engine's partial sums and psum): self time of the
operations under the round program's ``fl_aggregate`` scope, averaged over
the chips."""

from bench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "fl_aggregate")
