"""Mean duration of the window's saves' ``commit`` spans (manifest written
last, retention run)."""


def read(ctx):
    spans = ctx.traceutil.spans_from(ctx.spans, "commit", ctx.window[0])
    if not spans:
        return None
    return 1000.0 * sum(s["t1"] - s["t0"] for s in spans) / len(spans)
