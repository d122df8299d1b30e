"""Training loop + checkpoint/restart determinism + optimizer + data."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import CheckpointManager
from repro.data.pipeline import SyntheticTokenPipeline
from repro.optim.adamw import AdamWConfig, apply_updates, init_opt_state
from repro.training.loop import Trainer

# Whole-module slow marker: multi-second jit compiles per case; the
# fast lane (scripts/run_tests.sh --fast) deselects these.
pytestmark = pytest.mark.slow


def tiny_cfg():
    return smoke_variant(get_config("llama2-7b"))


def test_loss_decreases():
    tr = Trainer(tiny_cfg(), batch=2, seq_len=32,
                 hp=AdamWConfig(lr=3e-3, weight_decay=0.0))
    # repeat the same batch so the model can actually fit it
    batch = tr.pipeline.next_batch()
    tr.pipeline.next_batch = lambda: batch
    recs = tr.run(8)
    assert recs[-1].loss < recs[0].loss


def test_checkpoint_restart_is_deterministic(tmp_path):
    """Train 6 steps with a checkpoint at 3; a fresh trainer resumed from the
    checkpoint reproduces steps 4-6 losses exactly (globally consistent
    state: params + optimizer + data-iterator + step counter)."""
    cfg = tiny_cfg()
    mgr = CheckpointManager(str(tmp_path), mode="datastates")
    tr1 = Trainer(cfg, batch=2, seq_len=32, manager=mgr)
    recs1 = tr1.run(6, ckpt_interval=3)
    losses_after_3 = [r.loss for r in recs1 if r.step > 3]

    tr2 = Trainer(cfg, batch=2, seq_len=32, manager=mgr)
    resumed_step = tr2.resume(step=3)
    assert resumed_step == 3
    recs2 = tr2.run(3)
    losses_replayed = [r.loss for r in recs2]
    np.testing.assert_allclose(losses_replayed, losses_after_3,
                               rtol=1e-6, atol=1e-6)
    mgr.close()


def test_restart_across_engine_modes(tmp_path):
    """Checkpoints written by datastates-old restore identically."""
    cfg = tiny_cfg()
    mgr = CheckpointManager(str(tmp_path), mode="datastates-old")
    tr = Trainer(cfg, batch=2, seq_len=16, manager=mgr)
    tr.run(2, ckpt_interval=2)
    mgr.wait_for_persist()
    tr2 = Trainer(cfg, batch=2, seq_len=16, manager=mgr)
    tr2.resume()
    w1 = jax.tree_util.tree_leaves(tr.params)[0]
    w2 = jax.tree_util.tree_leaves(tr2.params)[0]
    np.testing.assert_array_equal(np.asarray(w1, dtype=np.float32),
                                  np.asarray(w2, dtype=np.float32))
    mgr.close()


def test_lazy_stall_accounted():
    cfg = tiny_cfg()
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, mode="datastates")
        tr = Trainer(cfg, batch=2, seq_len=16, manager=mgr)
        recs = tr.run(4, ckpt_interval=1)
        assert any(r.ckpt_requested for r in recs)
        assert all(r.ckpt_stall_s >= 0 for r in recs)
        mgr.close()


# ------------------------------------------------------------------ optimizer
def test_adamw_minimizes_quadratic():
    hp = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip=100.0)
    params = {"w": jnp.array([5.0, -3.0])}
    opt = init_opt_state(params)
    target = jnp.array([1.0, 2.0])
    for _ in range(200):
        g = {"w": 2 * (params["w"] - target)}
        params, opt = apply_updates(params, opt, g, hp)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_adamw_keeps_fp32_master_for_bf16_params():
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    opt = init_opt_state(params)
    assert opt["master"]["w"].dtype == jnp.float32
    g = {"w": jnp.full((4,), 1e-3, jnp.bfloat16)}
    new_params, new_opt = apply_updates(params, opt, g, AdamWConfig())
    assert new_params["w"].dtype == jnp.bfloat16
    assert new_opt["master"]["w"].dtype == jnp.float32
    # master moved even though the bf16 rounding may hide it
    assert float(jnp.abs(new_opt["master"]["w"] - 1.0).max()) > 0


def test_grad_clip_applies():
    hp = AdamWConfig(lr=1.0, grad_clip=1e-6, weight_decay=0.0)
    params = {"w": jnp.zeros((2,))}
    opt = init_opt_state(params)
    g = {"w": jnp.array([1e6, 1e6])}
    new_params, _ = apply_updates(params, opt, g, hp)
    assert float(jnp.abs(new_params["w"]).max()) < 2.0  # clipped, not 1e6


# ----------------------------------------------------------------------- data
def test_pipeline_deterministic_and_restorable():
    cfg = tiny_cfg()
    p1 = SyntheticTokenPipeline(cfg, 2, 16, seed=7)
    batches = [p1.next_batch() for _ in range(3)]
    state_after_2 = {"seed": 7, "step": 2}
    p2 = SyntheticTokenPipeline(cfg, 2, 16, seed=7)
    p2.restore(state_after_2)
    np.testing.assert_array_equal(p2.next_batch()["tokens"],
                                  batches[2]["tokens"])


def test_pipeline_shapes_for_modalities():
    for arch in ("paligemma-3b", "musicgen-medium"):
        cfg = smoke_variant(get_config(arch))
        p = SyntheticTokenPipeline(cfg, 2, 16)
        b = p.next_batch()
        if cfg.n_codebooks:
            assert b["tokens"].shape == (2, 16, cfg.n_codebooks)
            assert "memory_embeds" in b
        if cfg.n_prefix_embeds:
            assert b["prefix_embeds"].shape == (2, cfg.n_prefix_embeds,
                                                cfg.d_model)


def test_compile_cache_dir_from_env_or_fixed_repo_path(monkeypatch):
    """The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR`` wins
    and is left to JAX; otherwise one fixed directory in the repo root."""
    import os

    from repro.launch import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert compile_cache.enable_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir == was  # JAX's to read
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path  # no pid/time
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
