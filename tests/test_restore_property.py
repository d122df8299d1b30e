"""Property tests: arbitrary heterogeneous pytrees round-trip through every
engine format (the system invariant behind 'globally consistent state'),
and multi-rank saves round-trip across mesh/world shapes (elastic
restore — ISSUE 3 satellite)."""

import numpy as np
import pytest
from conftest import run_in_subprocess
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import ENGINES, CheckpointManager

DTYPES = [np.float32, np.float16, np.int32, np.int8, np.uint8, np.bool_]


@st.composite
def arrays(draw):
    dtype = draw(st.sampled_from(DTYPES))
    ndim = draw(st.integers(0, 3))
    shape = tuple(draw(st.integers(1, 5)) for _ in range(ndim))
    n = int(np.prod(shape)) if shape else 1
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    if dtype == np.bool_:
        return rng.integers(0, 2, size=shape).astype(np.bool_)
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        return rng.integers(info.min, info.max, size=shape).astype(dtype)
    return rng.normal(size=shape).astype(dtype)


scalars = st.one_of(st.integers(-2**31, 2**31), st.text(max_size=12),
                    st.floats(allow_nan=False), st.booleans(), st.none())

trees = st.recursive(
    st.one_of(arrays(), scalars),
    lambda kids: st.dictionaries(
        st.text(st.characters(categories=("Ll",)), min_size=1, max_size=6),
        kids, min_size=1, max_size=3),
    max_leaves=8)


def _assert_tree_equal(tree, out):
    import jax
    la, ta = jax.tree_util.tree_flatten(tree)
    lb, tb = jax.tree_util.tree_flatten(out)
    assert ta == tb
    for x, y in zip(la, lb):
        if isinstance(x, np.ndarray):
            np.testing.assert_array_equal(np.asarray(y), x)
            assert np.asarray(y).dtype == x.dtype
        elif isinstance(x, float):
            assert y == pytest.approx(x, nan_ok=True)
        else:
            assert y == x


@pytest.mark.parametrize("mode", sorted(ENGINES))
@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.function_scoped_fixture])
@given(tree=st.dictionaries(st.sampled_from(["a", "b", "c"]), trees,
                            min_size=1, max_size=3))
def test_roundtrip_any_tree(tmp_path_factory, mode, tree):
    d = tmp_path_factory.mktemp(f"prop_{mode}")
    with CheckpointManager(str(d), mode=mode) as mgr:
        mgr.save(1, tree, blocking=True)
        out = mgr.restore(tree, step=1)
    _assert_tree_equal(tree, out)


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.function_scoped_fixture])
@given(tree=st.dictionaries(st.sampled_from(["a", "b", "c"]), trees,
                            min_size=1, max_size=3),
       save_world=st.integers(1, 4), restore_world=st.integers(1, 4))
def test_roundtrip_any_tree_across_worlds(tmp_path_factory, tree,
                                          save_world, restore_world):
    """Multi-rank saves are format-compatible with any restore world: a
    tree saved by N writer ranks restores bit-exact under a manager with
    M ranks (restore is world-agnostic by construction)."""
    d = tmp_path_factory.mktemp(f"prop_w{save_world}_{restore_world}")
    with CheckpointManager(str(d), world=save_world,
                           manifest_checksums=False) as mgr:
        mgr.save(1, tree, blocking=True)
    with CheckpointManager(str(d), world=restore_world,
                           manifest_checksums=False) as mgr:
        out = mgr.restore(tree, step=1)
    _assert_tree_equal(tree, out)


@pytest.mark.slow
def test_reshard_roundtrip_mesh_grid():
    """Elastic multi-rank round-trip over a DP×TP mesh grid: save under
    one world shape (multi-rank coordinator), restore under a different
    mesh and sharding, assert bit-exact params (ISSUE 3 acceptance:
    N-rank save onto an M-rank mesh)."""
    out = run_in_subprocess(r"""
import itertools, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import CheckpointManager
from repro.launch.mesh import make_mesh

SHAPES = [(8, 1), (4, 2), (2, 4), (1, 8)]

def state_for(mesh):
    return {
        "w2d": jax.device_put(
            jnp.arange(64.0 * 48).reshape(64, 48),
            NamedSharding(mesh, P("data", "model"))),
        "zero1": jax.device_put(jnp.arange(96.0).reshape(32, 3) * 2,
                                NamedSharding(mesh, P("data", None))),
        "repl": jax.device_put(jnp.arange(40.0),
                               NamedSharding(mesh, P())),
        "meta": {"step": 1},
    }

def template_for(mesh):
    return {
        "w2d": jax.ShapeDtypeStruct((64, 48), jnp.float32,
                sharding=NamedSharding(mesh, P("model", "data"))),
        "zero1": jax.ShapeDtypeStruct((32, 3), jnp.float32,
                sharding=NamedSharding(mesh, P(None, None))),
        "repl": jax.ShapeDtypeStruct((40,), jnp.float32,
                sharding=NamedSharding(mesh, P("data"))),
        "meta": {"step": 0},
    }

for (sdp, stp), (rdp, rtp) in itertools.permutations(SHAPES, 2):
    if (sdp, stp) in ((2, 4), (1, 8)) and (rdp, rtp) not in ((4, 2), (8, 1)):
        continue  # trim the grid: keep every save shape + varied restores
    save_mesh = make_mesh((sdp, stp), ("data", "model"))
    restore_mesh = make_mesh((rdp, rtp), ("data", "model"))
    tmp = tempfile.mkdtemp()
    world = max(2, sdp // 2)
    with CheckpointManager(tmp, world=world,
                           manifest_checksums=False) as mgr:
        state = state_for(save_mesh)
        mgr.save(1, state, blocking=True)
        got = mgr.restore(template_for(restore_mesh), step=1)
        for key in ("w2d", "zero1", "repl"):
            np.testing.assert_array_equal(
                np.asarray(got[key], dtype=np.float32),
                np.asarray(state[key], dtype=np.float32),
                err_msg=f"{key}: save {(sdp, stp)}xW{world} "
                        f"-> restore {(rdp, rtp)}")
        assert got["meta"]["step"] == 1
print("RESHARD-GRID-OK")
""", timeout=900)
    assert "RESHARD-GRID-OK" in out
