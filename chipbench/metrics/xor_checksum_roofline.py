"""The fused XOR+digest kernel's share of its roofline: the HBM bytes its
calls need (two reads and one write of each padded chunk, from the
``encode.delta`` spans' chunk sizes) at the chip's peak bandwidth, over the
device time of the Pallas custom calls inside ``fused_xor_checksum``'s
jitted programs. Bound by bandwidth: it does no FLOPs."""

MODULE = "jit_fused_xor_checksum"


def read(ctx):
    if not ctx.device or not ctx.peak:
        return None
    chunks = ctx.traceutil.spans_from(ctx.spans, "encode.delta", ctx.window[0])
    calls = [e for e in ctx.traceutil.within(ctx.device, ctx.modules, MODULE)
             if e[0].endswith("custom-call")]
    busy = sum(b - a for _n, a, b in calls)
    if not chunks or busy <= 0:
        return None
    nbytes = sum(ctx.flops.xor_checksum_bytes(s["args"]["bytes"])
                 for s in chunks)
    return 100.0 * nbytes / ctx.peak["hbm_bytes_per_s"] / busy
