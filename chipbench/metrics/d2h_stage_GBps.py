"""Bytes over the union of the ``d2h.stage``-span intervals of the window's
saves, in GB/s."""


def read(ctx):
    return ctx.traceutil.span_rate(ctx.spans, "d2h.stage", ctx.window[0])
