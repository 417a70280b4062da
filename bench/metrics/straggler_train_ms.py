"""Device milliseconds per round training the straggler cohort: self time
of the operations under the round program's ``fl_straggler_train`` scope
(soft-training at volume P: Eq. 2 selection, masked local steps, scores),
averaged over the chips."""

from bench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "fl_straggler_train")
