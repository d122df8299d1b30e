"""Readings that the limits of ``correct`` are set from; not run by the
benchmark's own runs.

For each seed it builds a cell's set-up exactly as a run does (the
program's ``Trainer`` drives the first steps through its own call and
feed), reads the program's numbers against the float32 reference, and for
the first ``--control-seeds`` seeds also reads, against the same
reference, the control (the reference with every matmul input rounded to
float8) and the half-batch fault (the reference's loss averaged over the
first half of the rows). A state left unchanged reads 1 on ``change_gap``
by construction and needs no run. One JSON line per seed and reading.

    python3 chipbench/calibrate.py --workload sc2-save-raw \
        --seeds 11,12,13 --control-seeds 3
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import checks, harness, reference as ref, spec  # noqa: E402


def readings(run: "harness.Run", seed: int, control: bool):
    cfg = run.config
    batches = [run.feed.tokens(i) for i in range(harness.SETUP_STEPS)]
    p0 = ref.make_params(cfg, seed)
    want = ref.train_reference(cfg, p0, batches)
    out = [("program", checks.compare(
        prog_losses=run.prog_losses[:harness.SETUP_STEPS],
        extra_step3_losses=[],
        prog_grad=run.prog_grad, prog_change=run.prog_change, ref=want))]
    if control:
        for name, kw in (("control_fp8", {"lowp": True}),
                         ("fault_half_batch",
                          {"rows_used": max(1, batches[0].shape[0] // 2)})):
            got = ref.train_reference(cfg, p0, batches, **kw)
            out.append((name, checks.compare(
                prog_losses=list(got.losses), extra_step3_losses=[],
                prog_grad=got.grad_norms, prog_change=got.change_norms,
                ref=want)))
    harness.delete_arrays(p0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = spec.Cell(spec.benchmark(), args.workload)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX finds no TPU", file=sys.stderr)
        return 2
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell, seed, 0.0)
        try:
            run.setup()
            harness.delete_arrays((run.trainer.params,
                                   run.trainer.opt_state))
            for name, numbers in readings(run, seed,
                                          i < args.control_seeds):
                print(json.dumps({"workload": cell.name, "seed": seed,
                                  "reading": name, **numbers}), flush=True)
        finally:
            run.manager.close()
            harness.shutil.rmtree(run.ckpt_dir, ignore_errors=True)
            del run
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
