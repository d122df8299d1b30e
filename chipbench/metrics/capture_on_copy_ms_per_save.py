"""Time per save that the trainer was held at the capture barrier while the
staging lane copied landed bytes into the host cache: the overlap of the
window's ``ckpt.capture_barrier`` spans with the ``d2h.copy`` spans."""

from chipbench import spec


def read(ctx):
    return spec.load_module(spec.METRICS_DIR, "capture_on_transfer_ms_per_save"
                            ).held_during(ctx, "d2h.copy")
