"""Model FLOPs of the steps completed in the window, over the window's
seconds times the chip's bf16 peak, in %."""


def read(ctx):
    if not ctx["saves"] or not ctx["peaks"] or ctx["window_s"] <= 0:
        return None
    flops = ctx["flops_per_token"] * ctx["tokens"]
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
