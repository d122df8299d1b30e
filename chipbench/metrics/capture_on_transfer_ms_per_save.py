"""Time per save that the trainer was held at the capture barrier while the
staging lane waited for a device-to-host copy to land: the overlap of the
window's ``ckpt.capture_barrier`` spans with the ``d2h.wait`` spans."""


def held_during(ctx, name):
    """Milliseconds per save of the window's capture barriers that the
    ``name`` spans cover; ``None`` where the program records no such span."""
    tu = ctx.traceutil
    work = [(s["t0"], s["t1"]) for s in ctx.spans if s["name"] == name]
    if not ctx.saves or not work:
        return None
    held = sum(tu.union_length(tu.clip(work, s["t0"], s["t1"]))
               for s in tu.spans_from(ctx.spans, "ckpt.capture_barrier",
                                      ctx.window[0])
               if s["t0"] <= ctx.window[1])
    return 1000.0 * held / len(ctx.saves)


def read(ctx):
    return held_during(ctx, "d2h.wait")
