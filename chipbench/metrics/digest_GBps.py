"""Bytes over the union of the ``encode.digest``-span intervals of the window's
saves, in GB/s."""


def read(ctx):
    return ctx.traceutil.span_rate(ctx.spans, "encode.digest", ctx.window[0])
