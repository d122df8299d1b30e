"""Bytes the window's ``serve_load`` operations copied to the device over
the union of their ``restore.h2d`` spans, in GB/s."""

from chipbench import spec


def read(ctx):
    return spec.load_module(spec.METRICS_DIR, "restore_h2d_GBps.resume"
                            ).rate_in(ctx, "serve_load")
