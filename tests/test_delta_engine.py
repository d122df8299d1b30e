"""Differential checkpointing on the main engine path (ISSUE 4 tentpole).

Covers the save-side DeltaStateProvider (keyframe/delta modes, snapshot
cache inside the host-cache budget), the codec-aware flush stage (file
sizes shrink), chain metadata in the catalog, chain-aware retention GC,
whole-chain cascade, and bit-exact chain replay through RestoreEngine —
including hypothesis property tests over arbitrary dtypes/shapes and
chain lengths 1..2·keyframe_every.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import (CheckpointManager, DeltaPolicy, FileReader,
                        RestoreEngine, RestoreError)
from repro.core.state_provider import DELTA_CODEC
from repro.storage import MemoryBackend
from repro.storage.backend import BackendError
from repro.storage.repository import RetentionPolicy, Tier


def make_state(arrays, step=0):
    return {"model": dict(arrays), "meta": {"step": step, "tag": "delta"}}


def template_for(state):
    return {"model": {k: np.empty(np.asarray(v).shape, np.asarray(v).dtype)
                      for k, v in state["model"].items()},
            "meta": {"step": -1, "tag": ""}}


def mutate(state, step, frac=13):
    """Small sparse change — the slowly-moving-optimizer-state workload."""
    model = {}
    for k, v in state["model"].items():
        arr = np.array(np.asarray(v), copy=True)
        flat = arr.reshape(-1)
        if flat.size:
            if np.issubdtype(arr.dtype, np.floating):
                flat[::frac] += np.asarray(0.001, arr.dtype)
            else:
                flat[::frac] += 1
        model[k] = jnp.asarray(arr)
    return {"model": model, "meta": {"step": step, "tag": "delta"}}


def base_arrays():
    rng = np.random.default_rng(0)
    return {f"w{i}": jnp.asarray(rng.standard_normal(500 + 7 * i)
                                 .astype(np.float32))
            for i in range(3)}


def assert_bit_exact(restored, expected):
    for k, v in expected["model"].items():
        a = np.asarray(restored["model"][k])
        b = np.asarray(v)
        np.testing.assert_array_equal(a.reshape(-1).view(np.uint8),
                                      b.reshape(-1).view(np.uint8))


# ---------------------------------------------------------------- policy
def test_delta_policy_validation(tmp_path):
    with pytest.raises(ValueError, match="keyframe_every"):
        DeltaPolicy(keyframe_every=0)
    with pytest.raises(ValueError, match="DataMovementEngine"):
        CheckpointManager(str(tmp_path), mode="sync", delta=DeltaPolicy())


def test_chain_cadence_and_catalog_metadata(tmp_path):
    """keyframe_every=3 ⇒ k,d,d,k,d,... with base_step/chain_depth/codec
    recorded per step and per file in the catalog."""
    state = make_state(base_arrays())
    with CheckpointManager(str(tmp_path),
                           delta=DeltaPolicy(keyframe_every=3)) as mgr:
        for s in range(1, 6):
            state = mutate(state, s)
            mgr.save(s, state, blocking=True)
        expect = {1: (True, None, 0), 2: (False, 1, 1), 3: (False, 2, 2),
                  4: (True, None, 0), 5: (False, 4, 1)}
        for s, (kf, base, depth) in expect.items():
            d = mgr.repository.manifest(s).meta["delta"]
            assert d["keyframe"] is kf
            assert d["base_step"] == base
            assert d["chain_depth"] == depth
            assert d["codec"] == DELTA_CODEC
            codecs = {f.codec for f in mgr.repository.manifest(s).files
                      if f.name.endswith(".dsllm")}
            assert codecs == ({"raw"} if kf else {DELTA_CODEC})


def test_delta_files_smaller_and_restore_bit_exact(tmp_path):
    """Sparse mutations ⇒ delta steps far smaller than keyframes, and
    every step of the chain restores bit-exactly through the manager."""
    state = make_state(base_arrays())
    states = {}
    with CheckpointManager(str(tmp_path),
                           delta=DeltaPolicy(keyframe_every=4)) as mgr:
        for s in range(1, 7):
            state = mutate(state, s)
            states[s] = state
            mgr.save(s, state, blocking=True)
        key_bytes = mgr.repository.manifest(1).total_bytes
        delta_bytes = mgr.repository.manifest(2).total_bytes
        assert delta_bytes < key_bytes / 3
        for s in range(1, 7):
            out = mgr.restore(template_for(states[s]), step=s)
            assert_bit_exact(out, states[s])
            assert out["meta"]["step"] == s  # objects ride every save


def test_delta_step_cannot_be_restored_alone(tmp_path):
    state = make_state(base_arrays())
    with CheckpointManager(str(tmp_path),
                           delta=DeltaPolicy(keyframe_every=4)) as mgr:
        for s in (1, 2):
            state = mutate(state, s)
            mgr.save(s, state, blocking=True)
        sdir = mgr.repository.step_dir(2)
        with pytest.raises(RestoreError, match="delta-encoded"):
            RestoreEngine(threads=1).restore(sdir, template_for(state))
        # ...and FileReader refuses to hand out XOR-domain bytes as values
        f = [n for n in os.listdir(sdir) if n.endswith(".dsllm")][0]
        rd = FileReader(os.path.join(sdir, f))
        enc = [n for n, e in rd.tensors.items() if e.codec != "raw"]
        assert enc
        with pytest.raises(ValueError, match="chain"):
            rd.read_tensor(enc[0])


def test_reshard_forces_keyframe(tmp_path):
    """Changing the shard set / shapes between saves must break the chain
    with a fresh keyframe (elastic reshard rule)."""
    state = make_state(base_arrays())
    with CheckpointManager(str(tmp_path),
                           delta=DeltaPolicy(keyframe_every=10)) as mgr:
        mgr.save(1, mutate(state, 1), blocking=True)
        mgr.save(2, mutate(state, 2), blocking=True)
        assert not mgr.repository.manifest(2).meta["delta"]["keyframe"]
        rng = np.random.default_rng(1)
        resharded = make_state(
            {"w0": jnp.asarray(rng.standard_normal(777).astype(np.float32))},
            step=3)
        mgr.save(3, resharded, blocking=True)
        d = mgr.repository.manifest(3).meta["delta"]
        assert d["keyframe"] is True and d["base_step"] is None
        out = mgr.restore(template_for(resharded), step=3)
        assert_bit_exact(out, resharded)


def test_failed_save_invalidates_chain(tmp_path):
    """An engine failure mid-chain forces the next save back to a
    keyframe (the snapshot cache can no longer be trusted as a base)."""
    from repro.core import CheckpointError
    state = make_state(base_arrays())
    with CheckpointManager(str(tmp_path), host_cache_bytes=64 << 20,
                           delta=DeltaPolicy(keyframe_every=10)) as mgr:
        mgr.save(1, mutate(state, 1), blocking=True)
        mgr.save(2, mutate(state, 2), blocking=True)
        huge = make_state({"w0": np.zeros(200 << 20, np.uint8)})
        with pytest.raises(CheckpointError):
            mgr.save(3, huge, blocking=True)
        mgr.save(4, mutate(state, 4), blocking=True)
        assert mgr.repository.manifest(4).meta["delta"]["keyframe"] is True


# ------------------------------------------------------------ GC/cascade
def test_gc_keeps_whole_chain_of_retained_step(tmp_path):
    state = make_state(base_arrays())
    with CheckpointManager(str(tmp_path),
                           delta=DeltaPolicy(keyframe_every=4)) as mgr:
        states = {}
        for s in range(1, 7):  # k1 d2 d3 d4 k5 d6
            state = mutate(state, s)
            states[s] = state
            mgr.save(s, state, blocking=True)
        rep = mgr.repository.gc(retention=RetentionPolicy(keep_last_n=1))
        # keep-last-1 retains step 6; its chain pins keyframe 5 too
        assert rep.deleted_steps == [1, 2, 3, 4]
        assert mgr.repository.local_steps() == [5, 6]
        out = mgr.restore(template_for(states[6]), step=6)
        assert_bit_exact(out, states[6])


def test_pinned_delta_step_pins_whole_chain(tmp_path):
    state = make_state(base_arrays())
    with CheckpointManager(str(tmp_path),
                           delta=DeltaPolicy(keyframe_every=4)) as mgr:
        states = {}
        for s in range(1, 8):  # k1 d2 d3 d4 k5 d6 d7
            state = mutate(state, s)
            states[s] = state
            mgr.save(s, state, blocking=True)
        mgr.repository.pin(3)  # a mid-chain delta
        rep = mgr.repository.gc(retention=RetentionPolicy(keep_last_n=1))
        # pinned 3 pins 2 and keyframe 1; kept 7 pins 6 and keyframe 5
        assert set(rep.deleted_steps) == {4}
        assert mgr.repository.local_steps() == [1, 2, 3, 5, 6, 7]
        out = mgr.restore(template_for(states[3]), step=3)
        assert_bit_exact(out, states[3])


def test_cascade_ships_whole_chains_or_nothing(tmp_path):
    """A delta step only lands on a remote tier together with its
    ancestors; with the base gone everywhere, nothing ships."""
    from repro.storage import CheckpointRepository
    state = make_state(base_arrays())
    tier = Tier("mem", MemoryBackend())
    with CheckpointManager(str(tmp_path),
                           delta=DeltaPolicy(keyframe_every=4)) as mgr:
        for s in range(1, 4):  # k1 d2 d3
            state = mutate(state, s)
            mgr.save(s, state, blocking=True)
    repo = CheckpointRepository(str(tmp_path), remote_tiers=[tier],
                                auto_cascade=False)
    repo.cascade_step(3)  # ships 1 (keyframe), 2, 3
    assert repo.tier_steps(tier) == [1, 2, 3]
    # wipe the tier and the local keyframe: the chain can no longer ship
    for s in (1, 2, 3):
        repo._delete_tier_step(tier, s)
    repo._delete_local_step(1)
    with pytest.raises(BackendError, match="chain base"):
        repo.cascade_step(2)
    assert repo.tier_steps(tier) == []
    repo.close()


# ------------------------------------------------------- property tests
_DTYPES = (np.float32, np.float16, np.int32, np.uint8)


def _random_arrays(seed, n_tensors, odd):
    rng = np.random.default_rng(seed)
    out = {}
    for i in range(n_tensors):
        dtype = _DTYPES[int(rng.integers(len(_DTYPES)))]
        nd = int(rng.integers(0, 3))
        shape = tuple(int(rng.integers(1, 9)) for _ in range(nd))
        if odd and nd:  # force the odd-size u32-padding path
            shape = shape[:-1] + (shape[-1] * 2 + 1,)
        if np.issubdtype(dtype, np.floating):
            arr = rng.standard_normal(shape).astype(dtype)
        else:
            arr = rng.integers(0, 100, size=shape).astype(dtype)
        out[f"t{i}"] = jnp.asarray(arr)
    return out


def _chain_roundtrip(d, seed, n_tensors, keyframe_every, odd, n_saves):
    state = make_state(_random_arrays(seed, n_tensors, odd))
    states = {}
    with CheckpointManager(
            str(d), delta=DeltaPolicy(keyframe_every=keyframe_every),
            manifest_checksums=False) as mgr:
        for s in range(1, n_saves + 1):
            state = mutate(state, s, frac=3)
            states[s] = state
            mgr.save(s, state, blocking=True)
        for s in (1, n_saves):
            out = mgr.restore(template_for(states[s]), step=s)
            assert_bit_exact(out, states[s])


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.function_scoped_fixture])
@given(seed=st.integers(0, 2**31 - 1), n_tensors=st.integers(1, 4),
       keyframe_every=st.integers(1, 3), odd=st.booleans(),
       data=st.data())
def test_property_chain_replay_bit_exact(tmp_path_factory, seed, n_tensors,
                                         keyframe_every, odd, data):
    """Arbitrary dtypes/shapes (incl. odd sizes), chain lengths
    1..2·keyframe_every: every save restores bit-exactly."""
    n_saves = data.draw(st.integers(1, 2 * keyframe_every))
    _chain_roundtrip(tmp_path_factory.mktemp("delta-prop"), seed, n_tensors,
                     keyframe_every, odd, n_saves)


@pytest.mark.parametrize(
    "seed,n_tensors,keyframe_every,odd,n_saves",
    [(0, 3, 1, False, 2), (1, 2, 2, True, 4), (2, 4, 3, True, 6),
     (3, 1, 3, False, 1),
     # 0-d leaves: a float16 scalar (seed 1) and a float32 scalar (seed 3)
     (1, 3, 2, True, 3), (3, 2, 3, False, 4)])
def test_chain_replay_fixed_cases(tmp_path, seed, n_tensors, keyframe_every,
                                  odd, n_saves):
    """The property above pinned to fixed cases, including draws that once
    failed, so every run checks them."""
    _chain_roundtrip(tmp_path, seed, n_tensors, keyframe_every, odd, n_saves)


@pytest.mark.slow
def test_chain_restore_elastic_onto_sharded_mesh(tmp_path):
    """A delta chain saved single-device restores bit-exactly onto an
    8-way sharded target (multi-region buffers: every delta shard folds
    into several target regions)."""
    from conftest import run_in_subprocess
    run_in_subprocess(r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import CheckpointManager, DeltaPolicy
from repro.launch.mesh import make_mesh

d = %r
rng = np.random.default_rng(0)
state = {"model": {f"w{i}": jnp.asarray(
    rng.standard_normal((16, 24)).astype(np.float32)) for i in range(3)},
    "meta": {"step": 0}}
with CheckpointManager(d, delta=DeltaPolicy(keyframe_every=3)) as mgr:
    for s in range(1, 6):  # k d d k d
        state = {"model": {k: v.at[::5].add(0.25)
                           for k, v in state["model"].items()},
                 "meta": {"step": s}}
        mgr.save(s, state, blocking=True)
    mesh = make_mesh((8,), ("data",))
    shard = NamedSharding(mesh, P("data", None))
    tpl = {"model": {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shard)
                     for k, v in state["model"].items()},
           "meta": {"step": 0}}
    out = mgr.restore(tpl, step=5)
    for k, v in state["model"].items():
        got = np.asarray(out["model"][k])
        np.testing.assert_array_equal(got.view(np.uint8),
                                      np.asarray(v).view(np.uint8))
    assert len(out["model"]["w0"].sharding.device_set) == 8
print("elastic delta chain OK")
""" % str(tmp_path))
