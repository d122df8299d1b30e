"""Bytes over the union of the ``flush``-span intervals of the window's
saves, in GB/s."""


def read(ctx):
    return ctx.traceutil.span_rate(ctx.spans, "flush", ctx.window[0])
