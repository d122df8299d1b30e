"""Percent of the training window in which the chip sat idle while the
trainer's loop held the host: the idle gaps that the harness's gap labels
give to ``train.batch``, ``train.grad_dispatch``, ``train.update_dispatch``
or ``train.loss_sync``."""

LOOP = ("train.batch", "train.grad_dispatch", "train.update_dispatch",
        "train.loss_sync")


def read(ctx):
    tu = ctx.traceutil
    host = [(s["name"], s["t0"], s["t1"]) for s in ctx.spans
            if s["lane"] == "MainThread"] + \
        [(o["op"], o["t0"], o["t1"]) for o in ctx.ops]
    if not ctx.device or not any(name in LOOP for name, _a, _b in host):
        return None
    t0, t1 = ctx.window
    idle = tu.gaps([(a, b) for _n, a, b in ctx.device], t0, t1)
    labels = dict(tu.label_gaps(idle, host, n=len(host) + 1))
    return 100.0 * sum(labels.get(name, 0.0) for name in LOOP) / (t1 - t0)
