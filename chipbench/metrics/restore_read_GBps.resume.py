"""Bytes the restore engine read in the window's ``resume`` operations over
the union of their ``restore.read`` spans, in GB/s."""


def read(ctx):
    ops = [o for o in ctx.ops if o["op"] == "resume"]
    if not ops:
        return None
    spans = [(s["t0"], s["t1"]) for s in ctx.spans
             if s["name"] == "restore.read"
             and any(o["t0"] <= s["t0"] <= o["t1"] for o in ops)]
    return ctx.traceutil.rate(sum(o["bytes"] for o in ops), spans)
