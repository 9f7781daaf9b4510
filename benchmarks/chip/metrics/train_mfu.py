"""Model FLOP utilisation of the federated round, the whole step's share of
the chip's peak: the forward and backward FLOPs of every cohort slot's local
steps (the family's ``train_flops``, recomputation not counted) times the
rounds completed in the traced window, over the window times the chip's
bf16 peak."""


def read(ctx):
    flops = ctx["info"].get("train_flops_per_round")
    if not flops or ctx["busy_s"] <= 0:
        return None
    return 100.0 * flops * ctx["raw"]["rounds"] / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
