"""Model FLOPs per token times the window's tokens per second, over the
chip's bf16 peak, in %."""


def read(ctx):
    if ctx["saves"] or not ctx["peaks"] or not ctx["train_tokens_per_s"]:
        return None
    return 100.0 * ctx["flops_per_token"] * ctx["train_tokens_per_s"] / ctx["peaks"]["bf16_flops"]
