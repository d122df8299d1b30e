"""The capture barrier releases once every device shard has landed on the
host; the copy into the host cache may still be running. The donating update
that follows must not reach the saved bytes, and a copy that fails after the
barrier must still fail the save cleanly."""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CheckpointError, CheckpointManager, CheckpointPolicy,
                        DeltaPolicy, EnginePolicy, FileReader)
from repro.core.engine import DataMovementEngine


def host_state(step):
    rng = np.random.default_rng(step)
    return {"model": {"w": rng.standard_normal((64, 48)).astype(np.float32),
                      "b": rng.standard_normal(300).astype(jnp.bfloat16)},
            "optimizer": {"m": rng.standard_normal(5000).astype(np.float32),
                          "count": np.array(step, np.int32)}}


def device_state(step):
    """Fresh device copies of ``host_state(step)``, plus the meta object."""
    return dict(jax.tree.map(jnp.array, host_state(step)),
                meta={"step": step})


def policy(route):
    return CheckpointPolicy(
        engine=EnginePolicy(host_cache_bytes=1 << 22, chunk_bytes=1 << 12),
        delta=DeltaPolicy(keyframe_every=4) if route == "delta" else None)


class CopyGate:
    """Stands in for the copy lane's copy: holds every shard until released,
    then copies it, or raises for the ``fail_at``-th shard (1-based)."""

    def __init__(self, monkeypatch, fail_at=None):
        self.entered = threading.Event()
        self.release = threading.Event()
        self.copies = 0
        real = DataMovementEngine._copy_shard

        def copy(engine, provider, src):
            self.entered.set()
            assert self.release.wait(60)
            self.copies += 1
            if self.copies == fail_at:
                raise RuntimeError("copy into the host cache failed")
            real(engine, provider, src)

        monkeypatch.setattr(DataMovementEngine, "_copy_shard", copy)


def wait_for_capture_returns(mgr):
    waiter = threading.Thread(target=mgr.wait_for_capture, daemon=True)
    waiter.start()
    waiter.join(30)
    return not waiter.is_alive()


def bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("route", ["raw", "delta"])
def test_barrier_returns_before_the_copy_and_the_update_cannot_reach_it(
        tmp_path, monkeypatch, route):
    donating_update = jax.jit(lambda s: jax.tree.map(lambda x: x + 1, s),
                              donate_argnums=0)
    with CheckpointManager.from_policy(str(tmp_path), policy(route)) as mgr:
        mgr.save(1, device_state(1), blocking=True)    # delta: the keyframe
        gate = CopyGate(monkeypatch)
        state = device_state(2)
        try:
            fut = mgr.save(2, state)
            assert gate.entered.wait(30)
            assert wait_for_capture_returns(mgr)
            assert fut.captured and not fut.persisted
            updated = donating_update({"model": state["model"],
                                       "optimizer": state["optimizer"]})
            jax.block_until_ready(updated)
            del state
            assert all(a != b for a, b in zip(bits(updated),
                                              bits(host_state(2))))
        finally:
            gate.release.set()
        fut.wait_persisted(timeout=60)
        mgr.wait_for_commit(2)
        if route == "delta":
            assert mgr.repository.manifest(2).meta["delta"]["keyframe"] \
                is False
        out = mgr.restore(device_state(0), step=2)
    expected = host_state(2)
    assert bits({"model": out["model"], "optimizer": out["optimizer"]}) \
        == bits(expected)
    assert out["meta"] == {"step": 2}


def footerless(step_directory):
    left = []
    for f in glob.glob(os.path.join(step_directory, "*.dsllm")):
        try:
            FileReader(f)
        except (ValueError, OSError):
            left.append(f)
    return left


@pytest.mark.parametrize("route", ["raw", "delta"])
def test_copy_failing_after_capture_fails_the_save_and_frees_the_cache(
        tmp_path, monkeypatch, route):
    with CheckpointManager.from_policy(str(tmp_path), policy(route)) as mgr:
        cache = mgr.engine.host_cache
        if route == "delta":
            mgr.save(1, device_state(1), blocking=True)
        held = cache.used_bytes()          # the delta snapshot, else 0
        assert route == "delta" or held == 0
        # the last shard fails: a producer has read every other tensor and
        # is left waiting on that one, which only the failure can wake
        gate = CopyGate(monkeypatch,
                        fail_at=len(jax.tree.leaves(host_state(2))))
        try:
            fut = mgr.save(2, device_state(2))
            assert gate.entered.wait(30)
            assert wait_for_capture_returns(mgr)
        finally:
            gate.release.set()
        with pytest.raises(CheckpointError):
            fut.wait_persisted(timeout=60)
        mgr.wait_for_commit(2)
        assert not mgr.repository.has_manifest(2)
        # the failed file's lanes clean up a beat after the save fails
        directory = str(tmp_path / "global_step2")
        deadline = time.monotonic() + 10.0
        while (footerless(directory) or cache.used_bytes() != held) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert footerless(directory) == []
        assert cache.used_bytes() == held
        monkeypatch.undo()
        mgr.save(3, device_state(3), blocking=True)
        assert mgr.latest_step() == 3
        out = mgr.restore(device_state(0), step=3)
    assert bits({"model": out["model"], "optimizer": out["optimizer"]}) \
        == bits(host_state(3))
