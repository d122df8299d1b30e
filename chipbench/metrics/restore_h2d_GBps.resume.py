"""Bytes the window's ``resume`` operations copied to the device over the
union of their ``restore.h2d`` spans (assembly until the leaves are ready
on the chip), in GB/s."""


def rate_in(ctx, op):
    ops = [o for o in ctx.ops if o["op"] == op]
    spans = [s for s in ctx.spans if s["name"] == "restore.h2d"
             and any(o["t0"] <= s["t0"] <= o["t1"] for o in ops)]
    return ctx.traceutil.rate(sum(s["args"]["bytes"] for s in spans),
                              [(s["t0"], s["t1"]) for s in spans])


def read(ctx):
    return rate_in(ctx, "resume")
