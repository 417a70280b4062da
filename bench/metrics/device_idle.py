"""Share of the traced window in which no operation runs on the device,
averaged over the chips in use."""


def read(ctx):
    window_s = (ctx["hi"] - ctx["lo"]) * 1e-9
    return 100.0 * (1.0 - ctx["busy_s"] / window_s)
