"""Bring-up smoke run of the checkpointing main path on a TPU.

One chip (the default): Llama-3.2-1B at its published widths, depth cut to
4 layers, random weights from a fixed seed, batch 4 x 512 tokens.

* train  — ``Trainer`` + ``CheckpointManager.from_policy`` take a few steps
  with an asynchronous raw save every 2 steps and manifest checksums on;
* resume — the last step is re-verified with checksums (the Pallas
  checksum kernel on the chip), a fresh ``Trainer`` resumes it, every leaf
  digest matches the saved state and the next step's loss is identical;
* delta  — a keyframe then a delta save of the model domain (the fused
  XOR+digest kernel on the chip), and the delta step restores bit-exactly;
* serve  — greedy decoding from ``load_params_for_serving`` on the saved
  step gives the tokens the live parameters gave at that step.

``--chips 4`` runs only the sharded phase: the same state placed on a 2x2
mesh by the partition rules, saved, restored onto a 4x1 mesh, compared
bit-exactly, with every restored leaf spread over all four devices.

Exits non-zero, and prints no result, when JAX finds no TPU or any phase
fails. The last line of a passing run is one JSON object naming the
device. Other lines are bring-up observations, not benchmark metrics.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import resource
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llama3.2-1b"
N_LAYERS = 4         # the depth at which a resume's two state copies fit HBM
BATCH = 4
SEQ_LEN = 512
TRAIN_STEPS = 4
SAVE_EVERY = 2
N_NEW_TOKENS = 8
SEED = 0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def observe(phase: str, t0: float) -> None:
    """Print a phase's wall time and memory high-water marks."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    max_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    log(f"[observation] {phase}: wall {time.perf_counter() - t0:.3f} s, "
        f"device peak {'n/a' if peak is None else f'{peak / 2**30:.3f} GiB'}"
        f", host RSS {rss / 2**30:.3f} GiB (max {max_rss / 2**30:.3f} GiB)")


def smoke_config():
    from repro.configs import get_config, uniform_groups

    return dataclasses.replace(get_config(ARCH), n_layers=N_LAYERS,
                               layer_groups=uniform_groups("full", N_LAYERS))


def state_nbytes(cfg) -> int:
    """Bytes of one checkpoint version: params + optimizer state."""
    import jax

    from repro.models import model as M
    from repro.optim.adamw import init_opt_state

    shapes = jax.eval_shape(
        lambda k: (lambda p: (p, init_opt_state(p)))(M.init_params(cfg, k)),
        jax.random.PRNGKey(SEED))
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(shapes))


def array_leaves(tree):
    import jax

    return [(jax.tree_util.keystr(p), x)
            for p, x in jax.tree_util.tree_leaves_with_path(tree)
            if isinstance(x, jax.Array)]


def digests(tree) -> dict:
    """On-device checksum kernel over the bytes of every array leaf."""
    from repro.kernels import ops as kops

    return {k: int(kops.tensor_checksum(x)) for k, x in array_leaves(tree)}


def check_digests(got: dict, want: dict, what: str) -> None:
    check(got.keys() == want.keys(), f"{what}: leaf sets differ")
    bad = [k for k in want if got[k] != want[k]]
    check(not bad, f"{what}: {len(bad)} leaves differ, e.g. {bad[:3]}")


def delete_arrays(tree) -> None:
    """Free a state's device buffers now, whoever still refers to them."""
    for _k, x in array_leaves(tree):
        if not x.is_deleted():
            x.delete()


def abstract(tree):
    """Restore template: each array leaf as shape, dtype and sharding."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, tree)


def prompt_batch(cfg):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(SEED)
    return {"tokens": jnp.asarray(rng.integers(1, cfg.vocab, size=(1, 16)),
                                  jnp.int32)}


# ------------------------------------------------------------------ 1 chip
def run_one_chip(cfg, root: str, *, batch: int = BATCH,
                 seq_len: int = SEQ_LEN) -> None:
    import numpy as np

    from repro.core import (CheckpointManager, CheckpointPolicy, DeltaPolicy,
                            EnginePolicy, StateProviderRegistry,
                            StoragePolicy)
    from repro.serving.engine import greedy_generate, load_params_for_serving
    from repro.storage.repository import RetentionPolicy
    from repro.training.loop import Trainer

    version = state_nbytes(cfg)
    log(f"[observation] {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab}; one version {version} bytes")
    train_dir = os.path.join(root, "train")
    policy = CheckpointPolicy(
        engine=EnginePolicy(host_cache_bytes=version),
        storage=StoragePolicy(manifest_checksums=True,
                              retention=RetentionPolicy(keep_last_n=1)))
    prompt = prompt_batch(cfg)

    # -- train: async raw saves every SAVE_EVERY steps -------------------
    t0 = time.perf_counter()
    mgr = CheckpointManager.from_policy(train_dir, policy)
    trainer = Trainer(cfg, batch=batch, seq_len=seq_len, manager=mgr,
                      seed=SEED)
    records = trainer.run(TRAIN_STEPS, ckpt_interval=SAVE_EVERY)
    losses = [r.loss for r in records]
    check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    step = mgr.latest_step()
    check(step == TRAIN_STEPS, f"train: latest committed step {step}")
    check(not mgr.commit_errors, f"train: {mgr.commit_errors}")
    saved = digests(trainer.state())
    served = np.asarray(greedy_generate(cfg, trainer.params, prompt,
                                        N_NEW_TOKENS))
    log(f"train: {len(records)} steps, losses {losses}, saves at "
        f"{[r.step for r in records if r.ckpt_requested]}, step {step} "
        f"committed; first step {records[0].iter_s:.3f} s (with compile)")
    observe("train", t0)

    # -- resume: verify on the chip, then a fresh trainer -----------------
    t0 = time.perf_counter()
    verdict = mgr.repository.verify_step(step, check_checksums=True)
    check(verdict.ok, f"resume: verify_step({step}) found {verdict.problems}")
    log(f"resume: verify_step({step}) ok over "
        f"{len(mgr.repository.manifest(step).files)} files with checksums")
    next_loss = trainer.run(1)[-1].loss
    delete_arrays(trainer.state())
    mgr.close()
    del trainer, mgr
    gc.collect()  # the closed manager's host cache goes with it

    mgr = CheckpointManager.from_policy(train_dir, policy)
    trainer = Trainer(cfg, batch=batch, seq_len=seq_len, manager=mgr,
                      seed=SEED)
    check(trainer.resume() == step, "resume: wrong step")
    check_digests(digests(trainer.state()), saved, "resume")
    resumed_loss = trainer.run(1)[-1].loss
    check(resumed_loss == next_loss,
          f"resume: next loss {resumed_loss!r} != {next_loss!r}")
    log(f"resume: {len(saved)} leaf digests match; step {step + 1} loss "
        f"{resumed_loss!r} equal before and after resume")
    observe("resume", t0)

    # -- serve: the committed step against the live params of that step --
    t0 = time.perf_counter()
    params, rstats = load_params_for_serving(train_dir, trainer.params,
                                             step=step)
    tokens = np.asarray(greedy_generate(cfg, params, prompt, N_NEW_TOKENS))
    check(np.array_equal(tokens, served),
          f"serve: tokens {tokens.tolist()} != {served.tolist()}")
    log(f"serve: {rstats.bytes_read} bytes read; tokens {tokens.tolist()} "
        f"identical to the live params' at step {step}")
    delete_arrays(params)
    mgr.close()
    trainer.manager = None
    del mgr
    gc.collect()
    shutil.rmtree(train_dir)
    observe("serve", t0)

    # -- delta: keyframe, then a delta save one step later ----------------
    t0 = time.perf_counter()
    # A delta save holds the previous snapshot of every delta-routed leaf
    # beside the in-flight staging copy of the whole state. Snapshots of
    # every domain (two versions of host cache) ran a one-chip host (40 GiB)
    # out of memory, so only the model domain takes the delta route.
    registry = (StateProviderRegistry()
                .add_rule(provider="delta", domain="model")
                .add_rule(provider="tensor", kind="tensor")
                .add_rule(provider="object", kind="object"))
    snapshot = sum(x.nbytes for _k, x in array_leaves(trainer.params))
    log(f"delta: route restricted to the model domain ({snapshot} bytes of "
        f"snapshot); the optimizer state is saved raw")
    dmgr = CheckpointManager.from_policy(
        os.path.join(root, "delta"), CheckpointPolicy(
            engine=EnginePolicy(host_cache_bytes=version + snapshot),
            storage=StoragePolicy(manifest_checksums=True),
            delta=DeltaPolicy(keyframe_every=4), providers=registry))
    trainer.manager = dmgr
    trainer.run(2, ckpt_interval=1)
    key_step, delta_step = trainer.step - 1, trainer.step
    metas = [dmgr.repository.manifest(s).meta["delta"]
             for s in (key_step, delta_step)]
    check(metas[0]["keyframe"] and not metas[1]["keyframe"],
          f"delta: expected keyframe then delta, got {metas}")
    want = digests(trainer.state())
    template = abstract(trainer.state())
    delete_arrays(trainer.state())
    restored = dmgr.restore(template, step=delta_step)
    check_digests(digests(restored), want, "delta")
    log(f"delta: step {key_step} keyframe + step {delta_step} delta "
        f"restored; {len(want)} leaf digests match")
    dmgr.close()
    observe("delta", t0)


# ----------------------------------------------------------------- 4 chips
def run_four_chips(cfg, root: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import CheckpointManager, CheckpointPolicy, EnginePolicy
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.optim.adamw import init_opt_state
    from repro.sharding.partition import (opt_pspecs, param_pspecs,
                                          shardings_for)

    def random_state(key):
        params = M.init_params(cfg, key)
        opt = init_opt_state(params)
        # give m and v real bytes (a fresh optimizer's are all zero)
        leaves, tdef = jax.tree_util.tree_flatten(opt["m"])
        ks = jax.random.split(jax.random.fold_in(key, 1), 2 * len(leaves))
        opt["m"] = tdef.unflatten([
            jax.random.normal(k, x.shape, x.dtype)
            for k, x in zip(ks[:len(leaves)], leaves)])
        opt["v"] = tdef.unflatten([
            jax.random.uniform(k, x.shape, x.dtype)
            for k, x in zip(ks[len(leaves):], leaves)])
        return {"model": params, "optimizer": opt}

    def shardings(mesh):
        shapes = jax.eval_shape(random_state, jax.random.PRNGKey(SEED))
        return {"model": shardings_for(
                    param_pspecs(cfg, shapes["model"], mesh), mesh),
                "optimizer": shardings_for(
                    opt_pspecs(cfg, shapes["model"], mesh), mesh)}

    def bits(x):
        width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        return jax.lax.bitcast_convert_type(x, width)

    same_bits = jax.jit(lambda a, b: jnp.all(bits(a) == bits(b)))

    t0 = time.perf_counter()
    devices = jax.devices()
    src_mesh = make_mesh((2, 2), ("data", "model"))
    dst_mesh = make_mesh((4, 1), ("data", "model"))
    state = jax.jit(random_state, out_shardings=shardings(src_mesh))(
        jax.random.PRNGKey(SEED))
    version = state_nbytes(cfg)
    mgr = CheckpointManager.from_policy(
        os.path.join(root, "sharded"),
        CheckpointPolicy(engine=EnginePolicy(host_cache_bytes=version)))
    mgr.save(1, state, blocking=True)
    check(mgr.latest_step() == 1 and not mgr.commit_errors,
          f"sharded save: {mgr.commit_errors}")
    dst = shardings(dst_mesh)
    template = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, dst)
    restored = mgr.restore(template, step=1)
    src_leaves = dict(array_leaves(state))
    out_leaves = dict(array_leaves(restored))
    check(src_leaves.keys() == out_leaves.keys(), "sharded: leaf sets differ")
    dst_specs = {jax.tree_util.keystr(p): s
                 for p, s in jax.tree_util.tree_leaves_with_path(dst)}
    n_sharded = 0
    for k, x in src_leaves.items():
        y = out_leaves[k]
        check(bool(same_bits(x, y)), f"sharded: {k} differs after restore")
        on = sorted(d.id for d in y.sharding.device_set)
        check(y.sharding.device_set == set(devices),
              f"sharded: {k} sits on devices {on}")
        check(y.sharding.is_equivalent_to(dst_specs[k], y.ndim),
              f"sharded: {k} restored as {y.sharding}, "
              f"asked for {dst_specs[k]}")
        n_sharded += not y.sharding.is_fully_replicated
    mgr.close()
    log(f"sharded: {len(src_leaves)} leaves saved from a 2x2 mesh restored "
        f"bit-exact onto 4x1; all on {len(devices)} devices, {n_sharded} "
        f"split across them")
    observe("sharded", t0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded save/restore phase")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPU devices, JAX finds "
                         f"{len(devices)}")
    from repro.kernels import ops as kops

    check(not kops.host_fastpath(),
          "kernels would take the host path on a TPU backend")
    log(f"[observation] {len(devices)} x {devices[0].device_kind}; "
        f"compile cache {cache}")
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            run_four_chips(smoke_config(), root)
        else:
            run_one_chip(smoke_config(), root)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
