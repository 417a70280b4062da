"""Programs compiled (or read from the compilation cache) inside the traced
window, counted by ``jax.monitoring``; 0 when set-up warmed every shape."""


def read(ctx):
    return float(ctx["compiles"])
