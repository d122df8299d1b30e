"""Differential/compressed checkpointing (beyond-paper, kernel-backed)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core.reduction import (DifferentialCheckpointer, decode_tensor,
                                  encode_tensor)


def test_encode_decode_raw_lossless():
    x = jax.random.normal(jax.random.PRNGKey(0), (100, 37), jnp.float32)
    enc, work = encode_tensor(x)
    out = decode_tensor(enc)
    np.testing.assert_array_equal(out, np.asarray(x))


def test_encode_decode_delta_lossless():
    x0 = jax.random.normal(jax.random.PRNGKey(0), (1000,), jnp.float32)
    x1 = x0.at[::7].add(0.001)  # small sparse change
    enc0, w0 = encode_tensor(x0)
    enc1, _w1 = encode_tensor(x1, prev=w0)
    assert enc1.codec == "delta-xor"
    out = decode_tensor(enc1, prev=np.asarray(x0))
    np.testing.assert_array_equal(out, np.asarray(x1))


def test_delta_compresses_identical_state_massively():
    x = jax.random.normal(jax.random.PRNGKey(1), (1 << 16,), jnp.float32)
    _enc0, w0 = encode_tensor(x)
    enc1, _ = encode_tensor(x, prev=w0)          # unchanged -> all-zero XOR
    assert len(enc1.payload) < x.nbytes / 100    # >100x on the delta


def test_quantized_encode_bounded_error():
    x = jax.random.normal(jax.random.PRNGKey(2), (256, 256), jnp.float32)
    enc, _ = encode_tensor(x, quant="int8")
    assert enc.quant == "int8"
    out = decode_tensor(enc).astype(np.float32)
    # reconstruct with scales (stored as a zstd frame like the payload)
    from repro.core.reduction import _decompress
    scales = np.frombuffer(_decompress(enc.scales),
                           np.float32).reshape(256, 1)
    err = np.abs(out * scales - np.asarray(x))
    assert (err <= scales + 1e-6).all()


def test_differential_checkpointer_roundtrip(tmp_path):
    tree0 = {"a": jnp.arange(4096, dtype=jnp.float32),
             "b": {"c": jnp.ones((64, 64), jnp.float32)}}
    ck = DifferentialCheckpointer(str(tmp_path), keyframe_every=3)
    ck.save(0, tree0)
    tree1 = {"a": tree0["a"] + 1, "b": {"c": tree0["b"]["c"] * 2}}
    info1 = ck.save(1, tree1)
    assert not info1["keyframe"]
    tree2 = {"a": tree1["a"] * 0.5, "b": {"c": tree1["b"]["c"] - 3}}
    ck.save(2, tree2)

    for step, tree in ((0, tree0), (1, tree1), (2, tree2)):
        state = ck.restore(step)
        np.testing.assert_array_equal(state["['a']"], np.asarray(tree["a"]))
        np.testing.assert_array_equal(state["['b']['c']"],
                                      np.asarray(tree["b"]["c"]))


def test_differential_checkpointer_restart_continues_chain(tmp_path):
    """ISSUE-4 satellite bugfix: a restarted process must derive its
    keyframe/chain state from disk. Pre-fix, a restart reset _n_saves
    with an empty _prev, so a cadence-said-delta save was written
    ``keyframe=False`` while actually raw-encoded and restore() died on
    its ``chain[0]["keyframe"]`` assertion."""
    t0 = {"a": jnp.arange(1000, dtype=jnp.float32)}
    t1 = {"a": t0["a"].at[::9].add(1.0)}
    t2 = {"a": t1["a"].at[::9].add(1.0)}
    t3 = {"a": t2["a"].at[::9].add(1.0)}
    ck = DifferentialCheckpointer(str(tmp_path), keyframe_every=4)
    ck.save(0, t0)
    ck.save(1, t1)
    # process restart
    ck2 = DifferentialCheckpointer(str(tmp_path), keyframe_every=4)
    assert ck2._n_saves == 2  # cadence derived from disk
    info = ck2.save(2, t2)
    # the chain *continues* as deltas (bases re-armed from disk)...
    assert not info["keyframe"]
    import pickle
    with open(os.path.join(tmp_path, "diff_00000002.pkl"), "rb") as fh:
        rec = pickle.load(fh)
    assert all(e.codec == "delta-xor" for e in rec["tensors"].values())
    ck2.save(3, t3)
    # ...and every step restores across the restart boundary
    for step, tree in ((0, t0), (1, t1), (2, t2), (3, t3)):
        state = DifferentialCheckpointer(str(tmp_path)).restore(step)
        np.testing.assert_array_equal(state["['a']"], np.asarray(tree["a"]))


def test_differential_checkpointer_restart_with_damaged_tail(tmp_path):
    """If the on-disk chain tail is unreadable at restart, the next save
    must fall back to a keyframe (never a delta against nothing)."""
    t0 = {"a": jnp.arange(512, dtype=jnp.float32)}
    ck = DifferentialCheckpointer(str(tmp_path), keyframe_every=4)
    ck.save(0, t0)
    ck.save(1, {"a": t0["a"] + 1})
    for f in sorted(os.listdir(tmp_path)):  # corrupt every record
        with open(os.path.join(tmp_path, f), "r+b") as fh:
            fh.truncate(8)
    ck2 = DifferentialCheckpointer(str(tmp_path), keyframe_every=4)
    t2 = {"a": t0["a"] + 2}
    info = ck2.save(2, t2)
    assert info["keyframe"]  # forced: no usable bases on disk
    state = ck2.restore(2)
    np.testing.assert_array_equal(state["['a']"], np.asarray(t2["a"]))


# ----------------------------------------------- property-based round-trips
_PROP_DTYPES = ("float32", "float16", "int32", "uint8", "int8")


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       dtype=st.sampled_from(_PROP_DTYPES),
       shape=st.lists(st.integers(1, 17), min_size=0, max_size=3),
       n_deltas=st.integers(0, 3))
def test_property_encode_decode_roundtrip(seed, dtype, shape, n_deltas):
    """encode/decode is bit-exact for arbitrary dtypes/shapes (odd sizes
    exercise the u32-padding path) through raw and delta-chain codecs."""
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    shape = tuple(shape)
    if dt.kind == "f":
        arr = rng.standard_normal(shape).astype(dt)
    else:
        arr = rng.integers(0, 100, size=shape).astype(dt)
    enc, work = encode_tensor(jnp.asarray(arr))
    assert enc.codec == "raw"
    np.testing.assert_array_equal(decode_tensor(enc), arr)
    cur, prev_work, prev_dec = arr, work, arr
    for _ in range(n_deltas):
        nxt = np.array(cur, copy=True)
        flat = nxt.reshape(-1)
        if flat.size:
            idx = rng.integers(0, flat.size, size=max(1, flat.size // 7))
            flat[idx] += np.asarray(1, dt) if dt.kind != "f" \
                else np.asarray(0.5, dt)
        enc, work = encode_tensor(jnp.asarray(nxt), prev=prev_work)
        if cur.size:
            assert enc.codec == "delta-xor"
        dec = decode_tensor(enc, prev=np.asarray(prev_dec))
        np.testing.assert_array_equal(dec, nxt)
        cur, prev_work, prev_dec = nxt, work, dec


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       quant=st.sampled_from(("none", "bf16", "int8")),
       then_delta=st.booleans())
def test_property_quant_delta_codec_mixes(seed, quant, then_delta):
    """raw↔delta↔quant mixes: quantized encodes chain with deltas in the
    quantized working domain and decode returns that domain bit-exactly."""
    rng = np.random.default_rng(seed)
    x0 = rng.standard_normal((256, 256)).astype(np.float32)
    enc0, w0 = encode_tensor(jnp.asarray(x0), quant=quant)
    assert enc0.quant == quant
    dec0 = decode_tensor(enc0)
    np.testing.assert_array_equal(dec0,
                                  np.asarray(w0).reshape(dec0.shape))
    if not then_delta:
        return
    x1 = np.array(x0, copy=True)
    x1[::5] += 0.25
    enc1, _w1 = encode_tensor(jnp.asarray(x1), quant=quant, prev=w0)
    assert enc1.codec == "delta-xor"
    dec1 = decode_tensor(enc1, prev=dec0)
    _enc_ref, w1_ref = encode_tensor(jnp.asarray(x1), quant=quant)
    np.testing.assert_array_equal(dec1,
                                  np.asarray(w1_ref).reshape(dec1.shape))


def test_differential_smaller_than_full_for_slow_state(tmp_path):
    """Adam moments move slowly -> deltas ≪ keyframes."""
    base = jax.random.normal(jax.random.PRNGKey(3), (1 << 15,), jnp.float32)
    ck = DifferentialCheckpointer(str(tmp_path), keyframe_every=10)
    i0 = ck.save(0, {"m": base})
    i1 = ck.save(1, {"m": base})                 # unchanged
    assert i1["compressed_bytes"] < i0["compressed_bytes"] / 50


# ------------------------------------------------- zstd writer, zlib reader
# Payloads are always written as zstd frames. Checkpoints written by older
# installs without zstandard hold zlib frames, which must still read back.

import zlib

import zstandard

from repro.core import reduction as R


def test_zlib_fallback_roundtrip():
    """A tensor whose payload was written by the former zlib fallback
    decodes to the same bytes."""
    x = jax.random.normal(jax.random.PRNGKey(7), (333,), jnp.float32)
    enc, _work = encode_tensor(x)
    enc.payload = zlib.compress(R._decompress(enc.payload), 3)
    assert enc.payload[:4] != R._ZSTD_MAGIC  # really a zlib frame
    np.testing.assert_array_equal(decode_tensor(enc), np.asarray(x))


def test_zlib_payload_decodes_under_either_install():
    """_decompress sniffs the frame; it does not trust the writer's codec."""
    payload = zlib.compress(b"cross-install bytes" * 100, 3)
    assert R._decompress(payload) == b"cross-install bytes" * 100


def test_zstd_payload_roundtrip():
    """Encoding writes zstd frames, and they decode bit-exactly."""
    x = jax.random.normal(jax.random.PRNGKey(8), (222,), jnp.float32)
    enc, _ = encode_tensor(x)
    assert enc.payload[:4] == R._ZSTD_MAGIC
    np.testing.assert_array_equal(decode_tensor(enc), np.asarray(x))


def test_truncated_zstd_frame_raises():
    """A damaged zstd frame raises; it is never fed to zlib or returned."""
    frame = R._compress(b"some checkpoint bytes" * 64)
    with pytest.raises(zstandard.ZstdError):
        R._decompress(frame[:len(frame) // 2])


def test_differential_checkpointer_cross_codec_restore(tmp_path, monkeypatch):
    """Saves written with zlib frames restore identically — the whole
    differential chain (keyframe ⊕ deltas) survives a codec switch between
    save and restore."""
    tree0 = {"a": jnp.arange(512, dtype=jnp.float32)}
    tree1 = {"a": tree0["a"].at[::5].add(1.0)}
    monkeypatch.setattr(R, "_compress", zlib.compress)  # an older writer
    ck = DifferentialCheckpointer(str(tmp_path), keyframe_every=4)
    ck.save(0, tree0)
    ck.save(1, tree1)
    monkeypatch.undo()
    ck2 = DifferentialCheckpointer(str(tmp_path))
    state = ck2.restore(1)
    np.testing.assert_array_equal(state["['a']"], np.asarray(tree1["a"]))
