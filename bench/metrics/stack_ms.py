"""Host milliseconds per round building the round program's inputs: self
time of the program's ``fl.stack`` spans (stacking the cohort's batches and
Helios state, gathering population rows, the scheme's extras), placed on
the trace's clock by ``bench/program_trace.py``."""

from bench import program_trace


def read(ctx):
    return program_trace.span_ms(ctx, "fl.stack")
