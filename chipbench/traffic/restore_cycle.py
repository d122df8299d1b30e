"""Traffic kind ``restore_cycle``: set-up commits one version (at step
``setup_save_at``); the window cycles through ``window_ops`` until the
deadline, each op starting with the step's files dropped from the page
cache:

* ``resume``: ``Trainer.resume`` of that version plus one step, until the
  step's update is ready on the chip (``resume_s``);
* ``serve_load``: ``load_params_for_serving`` of the model domain, until
  the weights are ready on the chip (``replica_ready_s``).

Set-up runs each op once, untimed. Read-back: every op's state against
the state set-up saved (a resume's after its step against set-up's own
state after the same step).
"""

import statistics
import time

from chipbench import harness
from chipbench.fingerprint import fingerprint, mismatches

METRIC = {"resume": "resume_s", "serve_load": "replica_ready_s"}


def setup(run):
    run.saved = next(s for s in run.manager.saves
                     if s["step"] == run.setup_save_at)
    run.serve_template = harness.abstract(run.trainer.params)
    for op in run.traffic["window_ops"]:
        one_op(run, op)


def one_op(run, op):
    import jax

    from repro.serving.engine import load_params_for_serving

    trainer, step = run.trainer, run.setup_save_at
    harness.drop_page_cache(run.ckpt_dir)
    if op == "resume":
        t0 = time.perf_counter()
        trainer.resume(step=step)
        trainer.run(1)
        jax.block_until_ready((trainer.params, trainer.opt_state))
        t1 = time.perf_counter()
        return {"op": op, "t0": t0, "t1": t1,
                "bytes": trainer.last_resume_stats.bytes_read,
                "loss": trainer.records[-1].loss,
                "fp": fingerprint({"model": trainer.params,
                                   "optimizer": trainer.opt_state}),
                "want": run.fp_after_setup}
    if op == "serve_load":
        t0 = time.perf_counter()
        params, stats = load_params_for_serving(
            run.ckpt_dir, run.serve_template, step=step)
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        fp = fingerprint({"model": params})
        jax.block_until_ready(fp)
        harness.delete_arrays(params)
        return {"op": op, "t0": t0, "t1": t1, "bytes": stats.bytes_read,
                "fp": fp, "want": run.saved["fp"][:run.n_model]}
    raise ValueError(f"unknown window op {op!r}")


def window(run):
    ops = run.traffic["window_ops"]
    run.t0 = time.perf_counter()
    while time.perf_counter() < run.t0 + run.seconds:
        run.ops.append(one_op(run, ops[len(run.ops) % len(ops)]))
    run.t1 = time.perf_counter()


def end_to_end(run):
    out = {}
    for op in run.traffic["window_ops"]:
        times = [o["t1"] - o["t0"] for o in run.ops if o["op"] == op]
        if not times:
            raise SystemExit(f"chipbench: no {op} finished in the window")
        out[METRIC[op]] = statistics.fmean(times)
    return out


def verify(run):
    return sum(mismatches(o["fp"], o["want"]) for o in run.ops)
