"""Host milliseconds per round writing the round program's outputs back:
self time of the program's ``fl.writeback`` spans.  In the sharded engine
the copy into the host population rows waits there for the round program
to end."""

from bench import program_trace


def read(ctx):
    return program_trace.span_ms(ctx, "fl.writeback")
