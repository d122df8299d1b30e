"""Process-per-rank runtime: real SIGKILLs against the hierarchical commit.

The thread runtime's "dead rank" is a raised exception; here every rank
is a spawned OS process and the fault matrix kills it with an actual
``SIGKILL`` at each protocol window (``tests/faults.ProcessFaultSpec``,
fired child-side). The invariants under test (ISSUE 8 acceptance):

* a rank killed mid-save leaves **no visible step** — the orphan never
  enters the catalog, resume falls back to the previous commit;
* the failure is **isolated at the victim's aggregator**: the surviving
  node's aggregator still casts its ``NodeManifest`` vote (its subtree
  drained cleanly) while the victim's node poisons with the rank named;
* the coordinator evicts the corpse and the **next save commits with
  every shard present** — the dead rank's slice is re-spread over the
  survivors by byte balance — and a delta chain **re-keyframes**;
* per-process trace spans merge into the parent tracer so one Perfetto
  export covers every rank's lanes.

These run in the fast lane: spawn cost is ~1s/rank and the payloads are
tiny; the suite-wide slow-marker audit at the bottom pins that placement.
"""

from __future__ import annotations

import os
import re
import signal
import time

import numpy as np
import pytest

from repro.core import (CheckpointError, CheckpointManager,
                        CheckpointPolicy, DeltaPolicy, DistPolicy,
                        StoragePolicy)
from repro.dist import Coordinator, node_topology, partition_records
from repro.dist.coordinator import _SaveJob
from repro.obs import trace as obs
from repro.storage.manifest import read_node_manifests

from faults import ProcessDied, ProcessFaultSpec

WORLD = 4
NODE_SIZE = 2  # two nodes of two ranks: a real tree, still cheap


def _state(n_arrays: int = 8, per: int = 3000) -> dict:
    rng = np.random.default_rng(7)
    return {"model": {f"w{i:02d}": rng.standard_normal(per + i)
                      .astype(np.float32) for i in range(n_arrays)},
            "meta": {"note": "proc-runtime"}}


def _coordinator(fault=None, ack_timeout_s=60.0) -> Coordinator:
    return Coordinator(WORLD, runtime="process", node_size=NODE_SIZE,
                       host_cache_bytes=16 << 20, flush_threads=1,
                       checksum_files=False, ack_timeout_s=ack_timeout_s,
                       fault=fault)


def _manager(root: str, coordinator: Coordinator, **policy_kw
             ) -> CheckpointManager:
    return CheckpointManager.from_policy(root, CheckpointPolicy(
        storage=StoragePolicy(manifest_checksums=False),
        dist=DistPolicy(coordinator=coordinator), **policy_kw))


def _restore_template() -> dict:
    s = _state()
    return {"model": {k: np.zeros_like(v)
                      for k, v in s["model"].items()},
            "meta": {"note": ""}}


class TestHealthyHierarchicalCommit:
    def test_save_commits_with_node_manifests_and_topology_meta(
            self, tmp_path):
        state = _state()
        mgr = _manager(str(tmp_path), _coordinator())
        fut = mgr.save(1, state)
        fut.wait_persisted()
        mgr.wait_for_commit(1)
        assert mgr.commit_errors == []
        assert mgr.latest_step() == 1
        sdir = os.path.join(str(tmp_path), "global_step1")
        nodes = read_node_manifests(sdir)
        assert sorted(nodes) == [0, 1]
        assert nodes[0].ranks == [0, 1] and nodes[1].ranks == [2, 3]
        man = mgr.repository.manifest(1)
        assert man.meta["nodes"] == {"0": [0, 1], "1": [2, 3]}
        # full writer set → no degraded-writers record
        assert "writers" not in man.meta
        restored = mgr.restore(_restore_template())
        for k, v in state["model"].items():
            assert np.array_equal(restored["model"][k], v)
        mgr.close()

    def test_rank_children_never_start_an_accelerator_backend(
            self, tmp_path, monkeypatch):
        """Children inherit the parent's env, and the parent may hold a
        chip. A child told to use the TPU still runs its encode on the
        CPU: the delta save below asks each child's JAX for its backend."""
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        mgr = _manager(str(tmp_path), _coordinator(),
                       delta=DeltaPolicy(keyframe_every=4))
        state = _state()
        for step in (1, 2):
            state = {"model": {k: v + np.float32(step)
                               for k, v in state["model"].items()},
                     "meta": state["meta"]}
            mgr.save(step, state).wait_persisted()
            mgr.wait_for_commit(step)
        assert mgr.commit_errors == []
        assert mgr.repository.manifest(2).meta["delta"]["keyframe"] is False
        restored = mgr.restore(_restore_template(), step=2)
        for k, v in state["model"].items():
            assert np.array_equal(restored["model"][k], v)
        mgr.close()

    def test_process_traces_merge_into_parent_export(self, tmp_path):
        mgr = _manager(str(tmp_path), _coordinator())
        with obs.tracing() as t:
            fut = mgr.save(1, _state())
            fut.wait_persisted()
            mgr.wait_for_commit(1)
        lanes = {e["lane"] for e in t.events()}
        # child-side engine/vote spans shipped back, rank-labeled
        assert any(lane.startswith("rank000") for lane in lanes), lanes
        names = {e["name"] for e in t.events()}
        assert "vote" in names            # child-side phase-1 vote
        assert "node.vote" in names       # parent-side aggregator vote
        assert "rank.ship" in names       # payload crossing the pipe
        mgr.close()


class TestSigkillFaultMatrix:
    @pytest.mark.parametrize("point",
                             ["mid_file", "after_vote", "before_ack"])
    def test_killed_rank_leaves_no_visible_step_and_next_save_commits(
            self, tmp_path, point):
        state = _state()
        coord = _coordinator(
            fault=ProcessFaultSpec(point, rank=2, step=2))
        mgr = _manager(str(tmp_path), coord)
        f1 = mgr.save(1, state)
        f1.wait_persisted()
        mgr.wait_for_commit(1)

        f2 = mgr.save(2, state)
        with pytest.raises(CheckpointError) as ei:
            f2.wait_persisted()
        assert isinstance(ei.value.__cause__, ProcessDied)
        assert ei.value.__cause__.rank == 2
        mgr.wait_for_commit(2)
        # no visible step: the orphan never entered the catalog, resume
        # falls back to the previous commit
        assert mgr.latest_step() == 1
        restored = mgr.restore(_restore_template())
        for k, v in state["model"].items():
            assert np.array_equal(restored["model"][k], v)

        # the corpse is evicted; the next save commits with every shard
        # present on the surviving writers
        assert 2 in coord.dead_ranks
        f3 = mgr.save(3, state)
        f3.wait_persisted()
        mgr.wait_for_commit(3)
        assert mgr.commit_errors == []
        assert mgr.latest_step() == 3
        man = mgr.repository.manifest(3)
        assert man.meta["writers"] == [0, 1, 3]
        assert man.meta["nodes"] == {"0": [0, 1], "1": [3]}
        sdir = os.path.join(str(tmp_path), "global_step3")
        assert not os.path.exists(
            os.path.join(sdir, "rank00002.dsllm"))
        restored3 = mgr.restore(_restore_template())
        for k, v in state["model"].items():
            assert np.array_equal(restored3["model"][k], v)
        mgr.close()

    def test_failure_is_isolated_at_the_victims_aggregator(self,
                                                           tmp_path):
        """Rank 2 dies before its ack: its node (ranks 2-3) poisons with
        the victim named, while the *other* node's aggregator still
        drains its subtree and casts the node-0 vote into the (orphaned)
        step directory."""
        coord = _coordinator(
            fault=ProcessFaultSpec("after_upload", rank=2, step=1))
        mgr = _manager(str(tmp_path), coord)
        fut = mgr.save(1, _state())
        with pytest.raises(CheckpointError):
            fut.wait_persisted()
        mgr.wait_for_commit(1)
        assert mgr.latest_step() is None
        # surviving subtree completed phase 1 and its aggregator voted
        sdir = os.path.join(str(tmp_path), "global_step1")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            nodes = read_node_manifests(sdir)
            if 0 in nodes:
                break
            time.sleep(0.05)
        assert sorted(nodes) == [0], \
            "only the surviving node's aggregator should have voted"
        assert nodes[0].ranks == [0, 1]
        mgr.close()

    def test_stalled_rank_trips_watchdog_not_the_survivors(self,
                                                           tmp_path):
        coord = _coordinator(
            fault=ProcessFaultSpec("before_ack", rank=1, step=1,
                                   action="stall", stall_s=3.0),
            ack_timeout_s=1.0)
        mgr = _manager(str(tmp_path), coord)
        fut = mgr.save(1, _state())
        with pytest.raises(CheckpointError) as ei:
            fut.wait_persisted()
        assert "not all ranks acked" in repr(ei.value.__cause__)
        mgr.wait_for_commit(1)
        assert mgr.latest_step() is None
        mgr.close()

    def test_idle_rank_death_is_pruned_before_the_next_save(self,
                                                            tmp_path):
        """A rank that dies *between* saves (no failed save to flag it)
        is still evicted by the liveness probe at submit time."""
        state = _state()
        coord = _coordinator()
        mgr = _manager(str(tmp_path), coord)
        f1 = mgr.save(1, state)
        f1.wait_persisted()
        mgr.wait_for_commit(1)
        victim = coord.ranks[3]
        os.kill(victim._proc.pid, signal.SIGKILL)
        victim._proc.join(timeout=10)
        f2 = mgr.save(2, state)
        f2.wait_persisted()
        mgr.wait_for_commit(2)
        assert mgr.commit_errors == []
        assert mgr.repository.manifest(2).meta["writers"] == [0, 1, 2]
        restored = mgr.restore(_restore_template())
        for k, v in state["model"].items():
            assert np.array_equal(restored["model"][k], v)
        mgr.close()


class TestDeltaRekeyframeAfterDeath:
    def test_writer_loss_forces_a_keyframe(self, tmp_path):
        """Save 3 kills rank 1 mid-chain; the reassigned slice has no
        delta base on its new writer, so save 4 must re-keyframe (and
        commit)."""
        state = _state()
        coord = _coordinator(
            fault=ProcessFaultSpec("after_upload", rank=1, step=3))
        mgr = _manager(str(tmp_path), coord,
                       delta=DeltaPolicy(keyframe_every=100))
        f1 = mgr.save(1, state)
        f1.wait_persisted()
        mgr.wait_for_commit(1)
        assert mgr.repository.manifest(1).meta["delta"]["keyframe"]
        f2 = mgr.save(2, state)
        f2.wait_persisted()
        mgr.wait_for_commit(2)
        assert not mgr.repository.manifest(2).meta["delta"]["keyframe"]
        f3 = mgr.save(3, state)
        with pytest.raises(CheckpointError):
            f3.wait_persisted()
        mgr.wait_for_commit(3)
        assert mgr.latest_step() == 2
        f4 = mgr.save(4, state)
        f4.wait_persisted()
        mgr.wait_for_commit(4)
        assert mgr.commit_errors == []
        man4 = mgr.repository.manifest(4)
        assert man4.meta["delta"]["keyframe"]
        assert man4.meta["writers"] == [0, 2, 3]
        restored = mgr.restore(_restore_template())
        for k, v in state["model"].items():
            assert np.array_equal(restored["model"][k], v)
        mgr.close()


class TestDeadRankPartition:
    def test_orphan_slice_respreads_by_byte_balance(self):
        from repro.core.distributed import ShardRecord

        def rec(i, nbytes):
            return ShardRecord(
                leaf_path=f"t{i}", tensor_name=f"t{i:03d}", rank=0,
                index=((0, 1),), global_shape=(1,), shape=(1,),
                dtype="float32", nbytes=nbytes, data=None,
                device_resident=False)

        recs = [rec(i, 1000 + i) for i in range(16)]
        base = partition_records(recs, 4)
        degraded = partition_records(recs, 4, dead={2})
        assert sorted(degraded) == [0, 1, 3]
        # surviving ranks keep their base slice (delta bases stay valid)
        for r in (0, 1, 3):
            base_names = {x.tensor_name for x in base[r]}
            assert base_names <= {x.tensor_name for x in degraded[r]}
        # every orphaned record lands somewhere, exactly once
        all_names = sorted(x.tensor_name for p in degraded.values()
                           for x in p)
        assert all_names == sorted(x.tensor_name for x in recs)
        # byte balance: 4 orphans over 3 near-equally loaded survivors
        # (greedy, largest-first onto least-loaded) spreads them — every
        # survivor picks up work instead of one lane absorbing the slice
        added = {r: {x.tensor_name for x in degraded[r]} -
                 {x.tensor_name for x in base[r]} for r in (0, 1, 3)}
        assert all(added.values()), added

    def test_all_dead_raises(self):
        with pytest.raises(RuntimeError):
            partition_records([], 2, dead={0, 1})


class TestTopologyHelpers:
    def test_node_topology_blocks(self):
        assert node_topology(4, 2) == {0: [0, 1], 1: [2, 3]}
        assert node_topology(5, 2) == {0: [0, 1], 1: [2, 3], 2: [4]}
        # default: small worlds are single-node (flat-protocol behavior)
        assert node_topology(4) == {0: [0, 1, 2, 3]}

    def test_save_job_rejects_topology_not_covering_writers(self,
                                                            tmp_path):
        from repro.core.engine import CheckpointFuture
        with pytest.raises(ValueError):
            _SaveJob(1, str(tmp_path), 4, writers=[0, 1, 2],
                     nodes={0: [0, 1]},
                     future=CheckpointFuture(1, str(tmp_path)),
                     ack_timeout_s=None)


# Fast-lane placement audit: the process fault matrix must ride the fast
# lane (spawns are ~1s/rank), while the genuinely multi-minute suites
# stay behind the `slow` marker. This pins both sides so a stray
# pytestmark (or a missing one) shows up as a test failure, not as CI
# drift.
SLOW_MARKED_MODULES = {
    "test_distributed.py", "test_models.py", "test_perf_features.py",
    "test_system.py", "test_training.py",
}


def test_slow_marker_audit():
    tests_dir = os.path.dirname(__file__)
    for name in sorted(os.listdir(tests_dir)):
        if not (name.startswith("test_") and name.endswith(".py")):
            continue
        with open(os.path.join(tests_dir, name)) as f:
            src = f.read()
        module_slow = re.search(
            r"^pytestmark\s*=\s*pytest\.mark\.slow", src,
            re.MULTILINE) is not None
        assert module_slow == (name in SLOW_MARKED_MODULES), (
            f"{name}: module-level slow marker "
            f"{'present' if module_slow else 'missing'} but the audit "
            f"expects the opposite — update SLOW_MARKED_MODULES "
            f"deliberately if the lane placement really changed")
