"""Bytes over the union of the ``encode.delta``-span intervals of the window's
saves, in GB/s."""


def read(ctx):
    return ctx.traceutil.span_rate(ctx.spans, "encode.delta", ctx.window[0])
