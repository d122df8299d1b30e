"""Time the trainer was held per save in the window: the capture barrier
before the donating update plus the save's blocking prologue."""


def read(ctx):
    if not ctx.saves:
        return None
    held = sum(s["t1"] - s["t0"]
               for name in ("ckpt.capture_barrier", "save.prologue")
               for s in ctx.traceutil.spans_from(ctx.spans, name, ctx.window[0])
               if s["t0"] <= ctx.window[1])
    return 1000.0 * held / len(ctx.saves)
