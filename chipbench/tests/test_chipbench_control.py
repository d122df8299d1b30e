"""Clean runs of every tiny cell are correct; the control, the reference
in float8, reads far above the program on the same weights and tokens."""

import numpy as np
import pytest

from chipbench.tests import tiny
from chipbench import checks, harness, reference as ref


@pytest.mark.parametrize("workload", ["sc2-save-raw", "sc2-restore",
                                      "rwkv6-save-delta"])
def test_clean_run_is_correct(workload, tmp_path):
    out = tiny.run(workload, str(tmp_path))
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("workload", ["sc2-save-raw", "rwkv6-save-delta",
                                      "moe-save-raw"])
def test_control_reads_far_above_the_program(workload, tmp_path):
    seed = 2**31 + 99
    run = harness.Run(tiny.cell(workload), seed, 0.0, str(tmp_path))
    try:
        run.setup()
        cfg = run.config
        batches = [run.feed.tokens(i) for i in range(harness.SETUP_STEPS)]
        p0 = ref.make_params(cfg, seed)
        want = ref.train_reference(cfg, p0, batches)
        low = ref.train_reference(cfg, p0, batches, lowp=True)
    finally:
        run.manager.close()
        harness.shutil.rmtree(run.ckpt_dir, ignore_errors=True)
    prog = checks.compare(prog_losses=run.prog_losses[:harness.SETUP_STEPS],
                          extra_step3_losses=[], prog_grad=run.prog_grad,
                          prog_change=run.prog_change, ref=want)
    control = checks.compare(prog_losses=list(low.losses),
                             extra_step3_losses=[], prog_grad=low.grad_norms,
                             prog_change=low.change_norms, ref=want)
    limits = cfg["limits"]
    assert all(prog[k] <= limits[k] for k in prog), prog
    assert any(control[k] > limits[k] for k in control), control
    assert np.all(np.isfinite(low.losses))


def test_a_cell_without_saves_reports_no_commit_time(tmp_path):
    # a traffic mix with no save interval is data alone: sc2-train-nosave
    out = tiny.run("sc2-train-nosave", str(tmp_path))
    assert out["correct"] is True, out["checks"]
    assert "train_tokens_per_s" in out["metrics"]
    assert "save_commit_s" not in out["metrics"]


@pytest.mark.parametrize("policy,cache,delta", [
    ({}, 40, None),
    ({"host_cache_versions": 2}, 80, None),
    ({"routes": [{"provider": "delta", "domain": "model"}],
      "delta": {"keyframe_every": 4}}, 50, 4),
    ({"routes": [{"provider": "quantized", "domain": "model"}]}, 40, None),
])
def test_policy_is_built_from_the_traffic_file(policy, cache, delta):
    got = harness.make_policy(policy, {"model": 10, "optimizer": 30})
    assert got.engine.host_cache_bytes == cache
    assert (got.delta and got.delta.keyframe_every) == delta
    assert (got.providers is None) == ("routes" not in policy)
    assert got.storage.manifest_checksums is True
