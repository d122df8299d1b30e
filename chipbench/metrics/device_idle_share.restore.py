"""Percent of the restore window in which no operation ran on the chip."""


def read(ctx):
    if not ctx.device:
        return None
    return ctx.traceutil.idle_share([(a, b) for _n, a, b in ctx.device],
                                    *ctx.window)
