"""Whole step's share of the chips' bf16 peak over the traced window: the
window's trained tokens times the forward and backward operations a token
needs (recomputation not counted), over the window's seconds, the chips
and the peak.  Moves `train_tokens_per_s`."""


def read(ctx):
    if ctx.traced_tokens <= 0 or ctx.window_s <= 0:
        return None
    achieved = ctx.traced_tokens * ctx.flops_per_token / ctx.window_s
    return 100.0 * achieved / (ctx.chips * ctx.peak["bf16_flops_per_s"])
