"""Host milliseconds per round at the evaluation gate: self time of the
program's ``fl.evaluate`` spans (the evaluation chunk programs, their
per-chunk syncs, and the wait for the round program that precedes them)."""

from bench import program_trace


def read(ctx):
    return program_trace.span_ms(ctx, "fl.evaluate")
