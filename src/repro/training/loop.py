"""Two-phase training loop with lazy checkpoint integration (paper Fig 6).

The train step is split into ``grad_step`` (forward+backward — the *immutable
window*: params/opt state are only read) and ``update_step`` (the mutation
point — donates its buffers, the JAX analogue of in-place update). A
checkpoint requested at iteration end stages device→host concurrently with
the next iteration's grad_step; :meth:`CheckpointManager.wait_for_capture`
is called at the phase boundary so the donating update never overwrites
state still being snapshotted — exactly the paper's U-phase delay.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CheckpointManager
from repro.data.pipeline import SyntheticTokenPipeline
from repro.obs import trace as obs
from repro.models import model as M
from repro.optim.adamw import AdamWConfig, apply_updates, init_opt_state


def make_grad_step(cfg) -> Callable:
    def grad_step(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch))(params)
        return grads, loss
    return jax.jit(grad_step)


def make_update_step(cfg, hp: AdamWConfig) -> Callable:
    def update_step(params, opt_state, grads):
        return apply_updates(params, opt_state, grads, hp)
    # donate params+opt_state: the buffers being checkpointed are reused
    # in-place here — this is what makes the capture barrier necessary.
    return jax.jit(update_step, donate_argnums=(0, 1))


def make_train_step(cfg, hp: AdamWConfig) -> Callable:
    """Fused single-jit step (used by the dry-run / roofline path)."""
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch))(params)
        new_params, new_opt = apply_updates(params, opt_state, grads, hp)
        return new_params, new_opt, loss
    return train_step


@dataclasses.dataclass
class IterationRecord:
    step: int
    loss: float
    iter_s: float
    ckpt_stall_s: float       # direct stall (capture barrier + save prologue)
    ckpt_requested: bool


class Trainer:
    """End-to-end driver: data → two-phase step → lazy checkpoints."""

    def __init__(self, cfg, *, batch: int, seq_len: int,
                 hp: Optional[AdamWConfig] = None,
                 manager: Optional[CheckpointManager] = None,
                 seed: int = 0):
        self.cfg = cfg
        self.hp = hp or AdamWConfig()
        self.manager = manager
        self.pipeline = SyntheticTokenPipeline(cfg, batch, seq_len, seed=seed)
        self.grad_step = make_grad_step(cfg)
        self.update_step = make_update_step(cfg, self.hp)
        rng = jax.random.PRNGKey(seed)
        self.params = M.init_params(cfg, rng)
        self.opt_state = init_opt_state(self.params)
        self.step = 0
        self.records: List[IterationRecord] = []
        self.last_resume_stats = None  # RestoreStats from the last resume()
        self.exit_drain_s = 0.0        # end-of-run persist/commit wait

    # -- checkpoint state composition (the paper's heterogeneous pytree) ----
    def state(self) -> Dict[str, Any]:
        return {
            "model": self.params,
            "optimizer": self.opt_state,
            "meta": {
                "step": self.step,
                "arch": self.cfg.name,
                "data_state": self.pipeline.state,
                "hp": self.hp._asdict(),
                "rng": {"seed": 0},
            },
        }

    def resume(self, step: Optional[int] = None,
               fallback: Optional[bool] = None,
               domains: Optional[Tuple[str, ...]] = None) -> int:
        """Resume from a checkpoint via the parallel restore engine.

        Step selection goes through the manager's checkpoint repository:
        only *committed* steps (catalog manifest present, or legacy
        directories passing the completeness probe) are eligible, so a
        crash-interrupted save can never be resumed from; with
        ``step=None`` a damaged-but-committed step falls back to the
        previous complete one, and a step evicted from the local tier is
        re-hydrated from the first remote tier that holds it. Multi-rank
        saves (``CheckpointManager(world=N)``) follow the same rule — a
        step only commits once every writer rank acked its phase-1 vote,
        so a rank killed mid-save lands this resume on the previous
        committed step — and restore is elastic across worlds: an N-rank
        save resumes onto any M-rank mesh.

        Differential checkpoints (``CheckpointManager(delta=...)``)
        resume transparently: a delta step's chain (keyframe + every
        intermediate delta) is discovered from the catalog, re-verified
        against manifest checksums, and replayed bit-exactly — including
        the data-pipeline cursor and RNG objects, which ride every save
        in full, so a run resumed from a delta step reproduces the
        uninterrupted loss trajectory exactly
        (``tests/test_delta_faults.py::test_exact_resume_from_delta_step``).

        The manager's :class:`~repro.core.restore.RestoreEngine` indexes
        the step directory once, plans shard↔target intersections, and fans
        ranged reads out over a thread pool; per-phase timings land in
        ``self.last_resume_stats`` (index/read/assemble seconds plus the
        bytes actually read — the resume-cost breakdown of arXiv
        2512.24511).

        ``domains`` forwards to the manager's selective restore: e.g.
        ``resume(domains=("model",))`` reloads parameters only — the
        optimizer/meta domains keep this trainer's current values (and
        none of their bytes are read). Serving and full resume share
        this one catalog-driven path."""
        assert self.manager is not None
        restored = self.manager.restore(self.state(), step=step,
                                        fallback=fallback, domains=domains)
        self.params = restored["model"]
        self.opt_state = restored["optimizer"]
        self.step = restored["meta"]["step"]
        self.pipeline.restore(restored["meta"]["data_state"])
        self.last_resume_stats = self.manager.last_restore_stats
        return self.step

    def run(self, n_steps: int, ckpt_interval: int = 0) -> List[IterationRecord]:
        ckpt_pending = False
        for _ in range(n_steps):
            t0 = time.perf_counter()
            batch = {k: jnp.asarray(v)
                     for k, v in self.pipeline.next_batch().items()}
            t_batch = time.perf_counter()
            obs.add_span("train.batch", t0, t_batch)
            # --- immutable window: forward + backward ---------------------
            grads, loss = self.grad_step(self.params, batch)
            obs.add_span("train.grad_dispatch", t_batch, time.perf_counter())
            # --- capture barrier before the donating update ---------------
            stall = 0.0
            if ckpt_pending:
                t_b = time.perf_counter()
                stall = self.manager.wait_for_capture()
                obs.add_span("ckpt.capture_barrier", t_b, t_b + stall,
                             step=self.step)
                ckpt_pending = False
            t_u = time.perf_counter()
            self.params, self.opt_state = self.update_step(
                self.params, self.opt_state, grads)
            obs.add_span("train.update_dispatch", t_u, time.perf_counter())
            self.step += 1
            # --- checkpoint request (lazy: overlaps next fwd/bwd) ---------
            requested = False
            if ckpt_interval and self.manager is not None \
                    and self.step % ckpt_interval == 0:
                t_save = time.perf_counter()
                fut = self.manager.save(self.step, self.state())
                stall += time.perf_counter() - t_save  # blocking prologue
                ckpt_pending = True
                requested = True
            t_l = time.perf_counter()
            loss_val = float(loss)
            t1 = time.perf_counter()
            obs.add_span("train.loss_sync", t_l, t1)
            self.records.append(IterationRecord(
                step=self.step, loss=loss_val, iter_s=t1 - t0,
                ckpt_stall_s=stall, ckpt_requested=requested))
            obs.add_span("train.iteration", t0, t1, step=self.step,
                         stall_s=stall)
        self.exit_drain_s = 0.0
        if self.manager is not None:
            # End-of-run drain is blocking time too: without folding it
            # into the stall metric, a save requested on the last
            # iterations looks free (the old accounting stopped at the
            # save prologue, hiding the persist+commit wait here).
            t_d = time.perf_counter()
            self.manager.wait_for_persist()
            self.manager.wait_for_commit()
            self.exit_drain_s = time.perf_counter() - t_d
            obs.add_span("ckpt.exit_drain", t_d, t_d + self.exit_drain_s)
            if self.records and self.exit_drain_s > 0:
                last = self.records[-1]
                self.records[-1] = dataclasses.replace(
                    last, ckpt_stall_s=last.ckpt_stall_s
                    + self.exit_drain_s)
        return self.records
