"""Traffic kind ``save_cycle``: ``Trainer.run`` for the whole window under
the cell's ``CheckpointManager``, with a save requested every
``ckpt_interval`` steps (0 or absent: no saves). The window ends when the
token feed is asked for a batch after the deadline, and closes when the
last step's update is ready on the chip.

End-to-end: ``train_tokens_per_s``, every token trained over the window;
``save_commit_s``, the mean over the window's saves of the time from the
``save`` call to its commit (a save still in flight at the close is
drained and counted in full). Read-back: one version saved in the window,
drawn from the seed, restored through the program's repository path and
compared leaf by leaf with the state handed to the save.
"""

import statistics
import time

import numpy as np

from chipbench import harness
from chipbench.fingerprint import fingerprint, mismatches


def setup(run):
    pass


def window(run):
    import jax

    trainer = run.trainer
    run.t0 = time.perf_counter()
    step0 = trainer.step
    run.feed.deadline = run.t0 + run.seconds
    try:
        trainer.run(10 ** 9,
                    ckpt_interval=run.traffic.get("ckpt_interval", 0))
    except harness.WindowClosed:
        pass
    jax.block_until_ready((trainer.params, trainer.opt_state))
    run.t1 = time.perf_counter()
    run.feed.deadline = None
    run.steps = trainer.step - step0


def end_to_end(run):
    tr = run.traffic
    out = {"train_tokens_per_s": run.steps * tr["batch"] * tr["seq_len"]
           / (run.t1 - run.t0)}
    if tr.get("ckpt_interval"):
        saves = run.window_saves()
        if not saves:
            raise SystemExit("chipbench: no save was requested in the "
                             "window; the cell's interval is too long")
        out["save_commit_s"] = statistics.fmean(
            s["t_committed"] - s["t_call"] for s in saves)
    return out


def verify(run):
    """Leaves of a window version that read back differently from the
    state handed to its save, plus every leaf of a save that failed to
    commit. The chain of a delta version is replayed without the
    repository's re-verification of each member's whole-file checksums
    (a second read of every byte, through the kernel chunk by chunk);
    each encoded chunk's own digest is still checked as it is decoded."""
    from repro.core.checkpoint import restore_from_repository

    manager = run.manager
    template = harness.abstract(run.trainer.state())
    harness.delete_arrays((run.trainer.params, run.trainer.opt_state))
    failed = {step for step, _err in manager.commit_errors}
    saves = run.window_saves()
    bad = 0
    for s in saves:
        if s["step"] in failed:
            harness.log(f"version {s['step']} failed to commit")
            bad += len(np.asarray(s["fp"]))
    kept = [s for s in saves if s["step"] not in failed
            and manager.repository.has_manifest(s["step"])]
    if not kept:
        return bad
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 1]))
    s = kept[int(rng.integers(len(kept)))]
    try:
        restored, _stats, _step = restore_from_repository(
            manager.repository, template, step=s["step"],
            engine=manager.restore_engine, verify_chain=False)
    except Exception as exc:  # a version that fails to read back
        harness.log(f"version {s['step']} failed to restore: {exc!r}")
        return bad + len(np.asarray(s["fp"]))
    got = fingerprint({"model": restored["model"],
                       "optimizer": restored["optimizer"]})
    n = mismatches(got, s["fp"])
    meta = restored["meta"]
    if meta["step"] != s["step"] or \
            dict(meta["data_state"]) != s["data_state"]:
        n += 1
    harness.log(f"version {s['step']}: {n} of {len(np.asarray(got))} "
                f"leaves differ from the state handed to the save")
    harness.delete_arrays(restored)
    return bad + n
