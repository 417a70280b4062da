"""Required operations of the traced rounds over the traced window and the
chips' bf16 peak: every client's local forward and backward passes at the
sub-model its masks keep, and the evaluation forward pass
(``flops.RoundCost``)."""


def read(ctx):
    window_s = (ctx["hi"] - ctx["lo"]) * 1e-9
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["cost"].ops_per_round * ctx["rounds"] / window_s / peak
