"""Model FLOPs of the tokens trained in the window over the window and the
chip's bf16 peak, in percent."""


def read(ctx):
    if not ctx.tokens or not ctx.peak:
        return None
    t0, t1 = ctx.window
    return 100.0 * ctx.tokens * ctx.flops_per_token / (t1 - t0) \
        / ctx.peak["bf16_flops"]
