"""Device milliseconds per round training the capable cohort (full model):
self time of the operations under the round program's
``fl_capable_train`` scope, averaged over the chips."""

from bench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "fl_capable_train")
