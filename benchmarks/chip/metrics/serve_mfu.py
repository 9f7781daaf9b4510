"""Model FLOP utilisation of serving: the prefill and decode FLOPs of every
batch served in the traced window (the family's ``serve_flops``), over the
window times the chip's bf16 peak."""


def read(ctx):
    flops = ctx["info"].get("serve_flops")
    if not flops or ctx["busy_s"] <= 0:
        return None
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
