"""Percent of the time the window's delta encodes were open (the union of
their ``encode.delta`` spans) that their kernel round trips cover: the
``kernel.h2d``, ``kernel.call`` and ``kernel.d2h`` spans inside them, on
the same lane. The rest is the host's share: waiting for staged bytes,
the base XOR, the encode budget."""

ROUND_TRIP = ("kernel.h2d", "kernel.call", "kernel.d2h")


def read(ctx):
    tu = ctx.traceutil
    encodes = tu.spans_from(ctx.spans, "encode.delta", ctx.window[0])
    trips = [(k["t0"], k["t1"]) for k in ctx.spans
             if k["name"] in ROUND_TRIP
             and any(e["lane"] == k["lane"] and e["t0"] <= k["t0"]
                     and k["t1"] <= e["t1"] for e in encodes)]
    if not trips:
        return None
    return 100.0 * tu.union_length(trips) / tu.union_length(
        (e["t0"], e["t1"]) for e in encodes)
