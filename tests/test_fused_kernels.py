"""Differential harness for the one-pass fused encode/decode pipeline.

The fused path (``kernels/fused.py`` on TPU, the NumPy oracles in
``kernels/ref.py`` on host — selected by ``ops.host_fastpath()``) must be
*bit-identical* to the legacy multi-pass composition it replaced: separate
delta/quantize kernels followed by a second checksum pass over the encoded
payload. This module is that proof, plus the integration layers above it:

* fused Pallas kernels vs primitive-kernel composition vs NumPy oracles;
* codec round-trips (``encode_delta_chunk`` / ``encode_int8_block``) across
  dtypes and odd sizes, digest self-consistency, tamper detection;
* the streaming whole-file checksum vs the manifest's read-back hash under
  adversarial write patterns;
* real ``FileWriter``/``FileReader`` round-trips with per-chunk digests;
* the encode-budget contract: the encoded footprint is reserved exactly
  once per chunk, before the encode allocates it, and every staged byte is
  read exactly once (``engine.bytes_encode_read``).

Hypothesis property tests sit beside the parametrized fixed cases.
"""

import os
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.codecs import (CodecError, INT8_ROW_BYTES, INT8_ROW_ELEMS,
                               DELTA_CODEC, INT8_CODEC, decode_chunk_payload,
                               decode_int8_block, encode_delta_chunk,
                               encode_int8_block, int8_encoded_nbytes,
                               payload_digest)
from repro.core.layout import FileLayout, FileReader, FileWriter
from repro.core.reduction import _compress
from repro.core.state_provider import (DeltaStateProvider, EncodeBudget,
                                       QuantizedStateProvider)
from repro.kernels import ops, ref
from repro.obs.metrics import metrics as obs_metrics
from repro.storage.file_format import StreamingFileChecksum
from repro.storage.manifest import file_checksum

from hypothesis import given, settings
from hypothesis import strategies as st


def _bytes_case(nbytes: int, dtype, seed: int) -> np.ndarray:
    """Deterministic raw test bytes drawn through a typed array, so bit
    patterns exercise each dtype's value distribution (denormals, NaNs
    never matter — XOR/checksum are bit-domain)."""
    rng = np.random.default_rng(seed)
    if np.issubdtype(np.dtype(dtype), np.floating):
        arr = rng.standard_normal(-(-nbytes // np.dtype(dtype).itemsize)) \
            .astype(dtype)
    else:
        info = np.iinfo(dtype)
        arr = rng.integers(info.min, info.max,
                           -(-nbytes // np.dtype(dtype).itemsize),
                           dtype=dtype, endpoint=True)
    return arr.view(np.uint8)[:nbytes].copy()


# dtype sweep × odd sizes: u32-aligned, sub-word tail, single word, one byte
BYTE_CASES = [
    (65_536, np.float32), (70_004, np.float32),
    (12_345, np.int8), (7, np.int8),
    (4096, np.uint16),          # bf16-width lanes
    (99_991, np.uint32), (4, np.uint32), (1, np.uint8),
]


# ------------------------------------------------ fused kernels vs legacy
# Interpret-mode Pallas moves tens of MB/s — the arrays stay small; the
# codec-layer sweeps below cover size diversity at NumPy speed.
@pytest.mark.parametrize("n", [65_536, 70_000])
def test_fused_xor_checksum_matches_multipass(n):
    """One fused kernel call == legacy pass 1 (delta kernel) + legacy
    pass 2 (checksum kernel over the delta), bit for bit."""
    cur = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
    prev = jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32)
    delta_legacy = ops.delta_xor(cur, prev)           # pass 1
    dig_legacy = int(ops.tensor_checksum(delta_legacy))   # pass 2
    delta_fused, dig_fused = ops.fused_xor_checksum(cur, prev)
    np.testing.assert_array_equal(np.asarray(delta_fused),
                                  np.asarray(delta_legacy))
    assert int(dig_fused) == dig_legacy
    # and both equal the NumPy oracle the host fastpath dispatches to
    d_ref, dig_ref = ref.fused_xor_checksum_ref(
        np.asarray(cur).view(np.uint32), np.asarray(prev).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(delta_fused)[:n], d_ref)
    assert int(dig_fused) == dig_ref


def test_fused_xor_fold_matches_multipass():
    """Fused decode: fold(base, delta) == base ^ delta with the digest of
    the *delta* (what the footer stores), matching the two-pass read."""
    n = 70_000
    base = jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.float32)
    delta = jax.random.normal(jax.random.PRNGKey(4), (n,), jnp.float32)
    folded, dig = ops.fused_xor_fold(base, delta)
    want = np.bitwise_xor(np.asarray(base).view(np.uint32),
                          np.asarray(delta).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(folded)[:n], want)
    assert int(dig) == int(ops.tensor_checksum(ops.as_u32(delta)))
    f_ref, dig_ref = ref.fused_xor_fold_checksum_ref(
        np.asarray(base).view(np.uint32), np.asarray(delta).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(folded)[:n], f_ref)
    assert int(dig) == dig_ref


@pytest.mark.parametrize("rows", [256, 512])
def test_fused_quantize_matches_multipass(rows):
    """Fused quantize+digest vs the primitive quantize kernel plus a
    separate digest pass over what was actually emitted. q must be
    bit-exact; scales follow the repo's 1-ULP jit convention; the digest
    always describes the emitted (q, scales) payload area."""
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, INT8_ROW_ELEMS),
                          jnp.float32)
    q_legacy, s_legacy = ops.quantize_int8(x)     # legacy pass 1
    q, s, dig = ops.fused_quantize_int8(x, rows)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_legacy))
    np.testing.assert_allclose(np.asarray(s).reshape(-1),
                               np.asarray(s_legacy).reshape(-1), rtol=1e-6)
    # legacy pass 2 over the fused outputs == the fused digest
    assert int(dig) == ref.int8_payload_digest_ref(
        np.asarray(q), np.asarray(s), rows)


def test_fused_dequantize_matches_multipass():
    """int8→fp32 is one exactly-rounded multiply: the fused decode, the
    primitive kernel, and the oracle agree bit for bit — and the fused
    digest re-derives the stored payload's checksum during the decode."""
    rows = 256
    x = jax.random.normal(jax.random.PRNGKey(9), (rows, INT8_ROW_ELEMS),
                          jnp.float32)
    q, s = ops.quantize_int8(x)
    out, dig = ops.fused_dequantize_int8(q, s, rows)
    want = np.asarray(ops.dequantize_int8(q, s))
    np.testing.assert_array_equal(np.asarray(out), want)
    want_ref, dig_ref = ref.fused_dequantize_checksum_ref(
        np.asarray(q), np.asarray(s), rows)
    np.testing.assert_array_equal(np.asarray(out), want_ref)
    assert int(dig) == dig_ref == ref.int8_payload_digest_ref(
        np.asarray(q), np.asarray(s), rows)


def test_host_fastpath_checksum_equals_kernel():
    """The host fastpath's whole-tensor checksum (NumPy) and the Pallas
    checksum kernel are the same function."""
    for nbytes, dtype in [(70_004, np.float32), (12_345, np.int8)]:
        raw = _bytes_case(nbytes, dtype, seed=nbytes)
        assert ops.tensor_checksum_fast(raw) \
            == int(ops.tensor_checksum(jnp.asarray(raw)))


# -------------------------------------------------- codec layer round-trips
@pytest.mark.parametrize("nbytes,dtype", BYTE_CASES)
def test_delta_codec_roundtrip_and_digest(nbytes, dtype):
    cur = _bytes_case(nbytes, dtype, seed=10)
    prev = _bytes_case(nbytes, dtype, seed=11)
    delta, dig = encode_delta_chunk(cur, prev, with_digest=True)
    assert delta.nbytes == nbytes
    # digest == read-side oracle over the payload as stored
    assert dig == payload_digest(delta)
    # the no-digest path emits the identical payload
    delta2, dig2 = encode_delta_chunk(cur, prev, with_digest=False)
    assert dig2 is None
    np.testing.assert_array_equal(delta, delta2)
    # chain replay inverts it
    np.testing.assert_array_equal(np.bitwise_xor(prev, delta), cur)


@pytest.mark.parametrize("nbytes", [1 << 20, INT8_ROW_BYTES, 4097, 1000, 7])
def test_int8_codec_roundtrip_and_digest(nbytes):
    raw = _bytes_case((-(-nbytes // 4)) * 4, np.float32, seed=nbytes)[:nbytes]
    payload, dig = encode_int8_block(raw, with_digest=True)
    assert len(payload) == int8_encoded_nbytes(nbytes)
    # the fused digest covers the *whole* packed payload as stored —
    # header words included — so the read side can verify with one oracle
    assert dig == payload_digest(payload)
    out = decode_int8_block(payload, 0, nbytes, expect_digest=dig)
    assert out.nbytes == nbytes
    # bounded loss: one quantization step per fp32 value (whole rows only;
    # a sub-word tail can't view as fp32)
    if nbytes % 4 == 0:
        x = raw.view(np.float32)
        got = out.view(np.float32)
        pad = (-x.size) % INT8_ROW_ELEMS
        xp = np.concatenate([x, np.zeros(pad, np.float32)]) if pad else x
        step = np.abs(xp.reshape(-1, INT8_ROW_ELEMS)).max(axis=1) / 127
        step = np.repeat(step, INT8_ROW_ELEMS)[:x.size]
        assert (np.abs(got - x) <= step + 1e-7).all()
    # digest-off path: identical payload
    payload2, dig2 = encode_int8_block(raw, with_digest=False)
    assert dig2 is None and payload2 == payload


def test_int8_decode_rejects_tampered_payload():
    raw = _bytes_case(8192, np.float32, seed=77)
    payload, dig = encode_int8_block(raw, with_digest=True)
    # flip one bit inside the q area
    bad = bytearray(payload)
    bad[-100] ^= 0x40
    with pytest.raises(CodecError, match="digest mismatch"):
        decode_int8_block(bytes(bad), 0, 8192, expect_digest=dig)
    # a wrong stored digest is equally fatal
    with pytest.raises(CodecError, match="digest mismatch"):
        decode_int8_block(payload, 0, 8192,
                          expect_digest=(dig ^ 1) & 0xFFFFFFFF)
    # ...and without an expectation the decode still works (legacy footers)
    assert decode_int8_block(payload, 0, 8192).nbytes == 8192


def test_decode_dispatch_guards_chained_codecs():
    raw = _bytes_case(4096, np.float32, seed=5)
    payload, dig = encode_int8_block(raw, with_digest=True)
    out = decode_chunk_payload(INT8_CODEC, payload, 0, 4096,
                               expect_digest=dig)
    assert out.nbytes == 4096
    with pytest.raises(CodecError, match="chained"):
        decode_chunk_payload(DELTA_CODEC, b"\0" * 16, 0, 16)


# --------------------------------------------- streaming file checksum
def test_streaming_checksum_matches_manifest_hash(tmp_path):
    """The write-time accumulator must equal the manifest's read-back hash
    under every write pattern the writer produces: out-of-order pwrites,
    gaps (read as zeros), unaligned offsets/lengths, chunk-spanning runs."""
    patterns = [
        [(0, 123)],
        [(0, (4 << 20) + 517)],                      # spans a chunk seam
        [(4096, 1 << 16), (1 << 20, 77), (8, 3)],    # gap + out-of-order
        [(0, 4 << 20)],                              # exactly one chunk
        [(3, 7), (17, 1), (2 << 20, 4097)],          # unaligned everything
    ]
    for i, pat in enumerate(patterns):
        path = str(tmp_path / f"f{i}.bin")
        acc = StreamingFileChecksum()
        size = 0
        with open(path, "wb") as f:
            for j, (off, nb) in enumerate(pat):
                data = _bytes_case(nb, np.uint8, seed=100 * i + j)
                f.seek(off)
                f.write(data.tobytes())
                acc.update(off, data)
                size = max(size, off + nb)
            f.truncate(size)
        assert acc.value == file_checksum(path), f"pattern {i}: {pat}"


# ------------------------------------------- FileWriter/FileReader e2e
def _write_encoded_file(path, *, name, codec, chunks, nbytes,
                        track_checksum):
    """Drive the real writer the way a flush lane does: declare, compress,
    append with the fused digest, finalize."""
    w = FileWriter(path, FileLayout.plan([]), track_checksum=track_checksum)
    w.declare_encoded_tensor(name, dtype="uint8", shape=(nbytes,),
                             nbytes=nbytes, codec=codec)
    for payload, lo, hi, dig in chunks:
        w.append_encoded_chunk(name, _compress(bytes(payload)), lo, hi,
                               digest=dig)
    w.finalize()
    return w


def test_writer_reader_delta_roundtrip_with_digests(tmp_path):
    path = str(tmp_path / "d.dsllm")
    cur = _bytes_case(100_000, np.float32, seed=1)
    prev = _bytes_case(100_000, np.float32, seed=2)
    cut = 65_536
    chunks = []
    for lo, hi in [(0, cut), (cut, 100_000)]:
        delta, dig = encode_delta_chunk(cur[lo:hi], prev[lo:hi],
                                        with_digest=True)
        chunks.append((delta.tobytes(), lo, hi, dig))
    w = _write_encoded_file(path, name="t", codec=DELTA_CODEC,
                            chunks=chunks, nbytes=100_000,
                            track_checksum=True)
    # streamed == recomputed, without a second read of the file
    assert w.file_checksum == file_checksum(path)
    r = FileReader(path)
    entry = r.tensors["t"]
    assert [c[4] for c in entry.enc_chunks] == [c[3] for c in chunks]
    # tensor-level checksum derived for free from the chunk-digest fold
    want = 0
    for i, (_p, _lo, _hi, dig) in enumerate(chunks):
        want = (want + (i + 1) * dig) % (1 << 32)
    assert entry.checksum == want
    got = r.read_encoded_delta("t")
    np.testing.assert_array_equal(np.bitwise_xor(prev, got), cur)


def test_writer_reader_int8_roundtrip_with_digests(tmp_path):
    path = str(tmp_path / "q.dsllm")
    nbytes = 300_000
    raw = _bytes_case(nbytes, np.float32, seed=3)
    cut = 262_144  # a whole number of quantization rows
    chunks = []
    for lo, hi in [(0, cut), (cut, nbytes)]:
        payload, dig = encode_int8_block(raw[lo:hi], with_digest=True)
        chunks.append((payload, lo, hi, dig))
    w = _write_encoded_file(path, name="t", codec=INT8_CODEC,
                            chunks=chunks, nbytes=nbytes,
                            track_checksum=True)
    assert w.file_checksum == file_checksum(path)
    out = FileReader(path).read_encoded_tensor("t")
    assert out.nbytes == nbytes


def test_reader_rejects_tampered_shard(tmp_path):
    """Restore-side integrity: flipping one payload byte on disk fails the
    digest check during read, for both the chained and the self-contained
    codec."""
    for codec, make in [
        (DELTA_CODEC,
         lambda raw: encode_delta_chunk(raw, np.zeros_like(raw),
                                        with_digest=True)),
        (INT8_CODEC,
         lambda raw: encode_int8_block(raw, with_digest=True)),
    ]:
        path = str(tmp_path / f"{codec.split('+')[0]}.dsllm")
        raw = _bytes_case(65_536, np.float32, seed=4)
        payload, dig = make(raw)
        # the attack that only the fused digest can catch: a *validly
        # compressed* frame of a tampered payload, stored against the
        # original digest (a raw on-disk byte flip is already rejected by
        # the compression frame's own integrity check)
        bad = bytearray(bytes(payload))
        bad[len(bad) // 2] ^= 0x10
        w = FileWriter(path, FileLayout.plan([]))
        w.declare_encoded_tensor("t", dtype="uint8", shape=(65_536,),
                                 nbytes=65_536, codec=codec)
        w.append_encoded_chunk("t", _compress(bytes(bad)), 0, 65_536,
                               digest=dig)
        w.finalize()
        r = FileReader(path)
        with pytest.raises(ValueError, match="mismatch"):
            if codec == DELTA_CODEC:
                r.read_encoded_delta("t")
            else:
                r.read_encoded_tensor("t")


def test_legacy_four_tuple_footers_still_read(tmp_path):
    """Pre-digest footers carry 4-tuple enc_chunks; the reader normalizes
    them to digest=None and skips verification."""
    import msgpack
    path = str(tmp_path / "legacy.dsllm")
    raw = _bytes_case(4096, np.float32, seed=6)
    payload, _ = encode_int8_block(raw, with_digest=False)
    w = FileWriter(path, FileLayout.plan([]))
    w.declare_encoded_tensor("t", dtype="uint8", shape=(4096,),
                             nbytes=4096, codec=INT8_CODEC)
    w.append_encoded_chunk("t", _compress(bytes(payload)), 0, 4096)
    w.finalize()
    # rewrite the footer with 4-tuple chunks, as an old writer laid it out
    r = FileReader(path)
    footer = r.footer
    for t in footer["tensors"]:
        t["enc_chunks"] = [list(c[:4]) for c in t["enc_chunks"]]
    fpay = msgpack.packb(footer, use_bin_type=True)
    trailer = struct.Struct("<Q8s")
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size - trailer.size)
        old_len, magic = trailer.unpack(f.read(trailer.size))
        f.seek(size - trailer.size - old_len)
        f.write(fpay)
        f.write(trailer.pack(len(fpay), magic))
        f.truncate()
    r2 = FileReader(path)
    assert r2.tensors["t"].enc_chunks[0][4] is None
    assert r2.read_encoded_tensor("t").nbytes == 4096


# -------------------------------------------------- encode-budget contract
class _RecordingBudget(EncodeBudget):
    def __init__(self, cap):
        super().__init__(cap)
        self.acquires = []
        self.peak = 0

    def acquire(self, nbytes):
        self.acquires.append(nbytes)
        super().acquire(nbytes)
        self.peak = max(self.peak, self._used)


def test_quantized_budget_reserves_encoded_footprint_once():
    """Regression for the double-reservation bug: each fused chunk must
    reserve exactly its *encoded* footprint (known a priori), exactly
    once — not once per legacy pass, and not the raw size."""
    n = 6 * INT8_ROW_BYTES + 4  # two full chunks + a one-row tail chunk
    arr = _bytes_case(n, np.float32, seed=8).view(np.float32)
    sp = QuantizedStateProvider("q", dtype="float32", shape=(arr.size,),
                                nbytes=n, host_array=arr,
                                chunk_bytes=3 * INT8_ROW_BYTES)
    budget = _RecordingBudget(cap=1 << 30)
    sp.encode_budget = budget
    spans = [(lo, min(lo + sp.chunk_bytes, n))
             for lo in range(0, n, sp.chunk_bytes)]
    want = [int8_encoded_nbytes(hi - lo) for lo, hi in spans]
    chunks = []
    for c in sp.chunks():
        chunks.append(c)
        assert len(c.data) == int8_encoded_nbytes(
            c.raw_range[1] - c.raw_range[0])
        c.on_flushed()  # flush lane credits back immediately
    assert budget.acquires == want
    # with immediate flush the pool never holds more than one chunk
    assert budget.peak == max(want)
    assert budget._used == 0


def test_delta_budget_and_single_read_of_staged_bytes():
    """A mixed delta save reads each staged byte exactly once
    (``engine.bytes_encode_read``), reserves each delta chunk once, and
    advances the snapshot base to the current bytes without re-reading
    the staged view."""
    n = 200_000
    cur = _bytes_case(n, np.float32, seed=12)
    prev_store = _bytes_case(n, np.float32, seed=13)
    prev_copy = prev_store.copy()
    sp = DeltaStateProvider("d", dtype="uint8", shape=(n,), nbytes=n,
                            host_array=cur, prev=memoryview(prev_store),
                            keyframe=False, chunk_bytes=65_536)
    sp.checksum_chunks = True
    budget = _RecordingBudget(cap=1 << 30)
    sp.encode_budget = budget
    before = obs_metrics.snapshot()["counters"] \
        .get("engine.bytes_encode_read", 0)
    out = []
    for c in sp.chunks():
        assert c.digest == payload_digest(np.asarray(c.data))
        out.append(c)
        c.on_flushed()
    read = obs_metrics.snapshot()["counters"]["engine.bytes_encode_read"] \
        - before
    assert read == n                       # one read per staged byte
    assert budget.acquires == [min(65_536, n - lo)
                               for lo in range(0, n, 65_536)]
    assert budget._used == 0
    # the chain base advanced to cur (base ^ delta), bit-exactly
    np.testing.assert_array_equal(prev_store, cur)
    # and the emitted deltas replay against the *old* base
    folded = prev_copy.copy()
    for c in out:
        lo, hi = c.raw_range
        np.bitwise_xor(folded[lo:hi], np.asarray(c.data),
                       out=folded[lo:hi])
    np.testing.assert_array_equal(folded, cur)


# ------------------------------------------------------- property tests
@given(st.integers(1, 65_536), st.integers(0, 2 ** 32 - 1))
@settings(max_examples=30, deadline=None)
def test_prop_delta_digest_is_payload_digest(nbytes, seed):
    cur = _bytes_case(nbytes, np.uint8, seed=seed & 0xFFFF)
    prev = _bytes_case(nbytes, np.uint8, seed=(seed >> 16) | 1)
    delta, dig = encode_delta_chunk(cur, prev, with_digest=True)
    assert dig == payload_digest(delta)
    np.testing.assert_array_equal(np.bitwise_xor(prev, delta), cur)


@given(st.integers(1, 32_768))
@settings(max_examples=20, deadline=None)
def test_prop_int8_payload_digest_roundtrip(nbytes):
    raw = _bytes_case((-(-nbytes // 4)) * 4, np.float32,
                      seed=nbytes)[:nbytes]
    payload, dig = encode_int8_block(raw, with_digest=True)
    assert dig == payload_digest(payload)
    assert decode_int8_block(payload, 0, nbytes,
                             expect_digest=dig).nbytes == nbytes
