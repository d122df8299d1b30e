"""Tensor chunk codecs for encoded (non-raw) checkpoint payloads.

The engine's flush lanes treat any chunk with ``codec != "raw"`` the same
way: compress the producer-encoded payload and log-append it with explicit
raw-range addressing (``layout.FileWriter.append_encoded_chunk``). What
differs per codec is (a) how the producer turns raw tensor bytes into the
payload and (b) how a reader turns the decompressed payload back into raw
bytes — and, crucially, whether that inversion is *self-contained* or
*chained*:

* **chained** codecs (``xor+zstd`` — differential checkpointing) encode a
  chunk relative to a previous checkpoint's bytes; their payloads only
  have meaning during chain replay (``RestoreEngine.restore_chain``).
* **self-contained** codecs (``int8q+zstd`` — blockwise int8 quantization
  of fp32 state, built on the Pallas kernels in ``kernels/quantize.py``)
  decode standalone, so a quantized tensor restores like any raw tensor,
  including through selective (per-domain) restore.

This module is the single registry both sides consult: providers name a
codec on each :class:`~repro.core.state_provider.Chunk`, and
``layout.FileReader`` / ``core.restore`` dispatch decode through
:func:`decode_chunk_payload` / classify through :func:`is_chained_codec`.

Encode is **one-pass** (``kernels/fused.py``): each route's encoder returns
``(payload, digest)`` from a single read of the staged bytes — the digest is
the position-weighted u32 checksum of the uncompressed payload, stored per
chunk in the file footer and re-verified on decode. On a real TPU the fused
Pallas kernels produce payload + digest in one kernel invocation; without
one, the bit-identical NumPy oracles in ``kernels/ref.py`` run instead
(interpret-mode Pallas is a correctness harness, ~20 MB/s). Digests are
skipped (``None``) when the save runs with manifest checksums disabled.

``int8q`` payload layout (before the flush lane's zstd/zlib compression),
covering raw fp32 bytes ``[raw_lo, raw_hi)`` of the tensor:

    u32 n_rows | u32 raw_nbytes | f32 scales[n_rows] | i8 q[n_rows * 256]

Rows are the kernel's native (256-lane) quantization rows: the raw bytes
are viewed as fp32, padded to whole rows, and each row gets a symmetric
per-row scale ``max|x|/127``. Decode dequantizes and truncates the pad.
"""

from __future__ import annotations

import struct
from typing import Callable, Dict

import numpy as np

#: fp32 elements per quantization row (the Pallas kernel's lane width).
INT8_ROW_ELEMS = 256
#: raw bytes per quantization row.
INT8_ROW_BYTES = INT8_ROW_ELEMS * 4
#: the kernel's row-tile granularity (grid dimension), see kernels/quantize.
_KERNEL_ROW_TILE = 256

_INT8_HEADER = struct.Struct("<II")

DELTA_CODEC = "xor+zstd"
INT8_CODEC = "int8q+zstd"


class CodecError(ValueError):
    """A payload failed to decode (corrupt, truncated, or wrong codec)."""


def codec_base(codec: str) -> str:
    """``"int8q+zstd"`` → ``"int8q"`` (strip the host-compression suffix)."""
    return codec.split("+", 1)[0]


def is_chained_codec(codec: str) -> bool:
    """True for codecs whose payloads only decode relative to a chain base
    (differential XOR deltas); such tensors cannot restore standalone."""
    return codec != "raw" and codec_base(codec) == "xor"


# ------------------------------------------------------------ chunk digests

def _header_digest(n_rows: int, raw_nbytes: int) -> int:
    """Digest contribution of the two ``int8q`` header words (idx 0 and 1)."""
    from repro.kernels.checksum import WEIGHT_BASE
    return (n_rows * WEIGHT_BASE + raw_nbytes * (WEIGHT_BASE + 1)) \
        & 0xFFFFFFFF


def payload_digest(payload) -> int:
    """Position-weighted u32 digest of an uncompressed payload's bytes.

    The read-side oracle: every fused encoder's digest equals this function
    over the payload it emitted (``tests/test_fused_kernels.py`` is the
    proof)."""
    from repro.kernels import ref as kref

    return kref.checksum_np_bytes(payload)


def int8_encoded_nbytes(raw_nbytes: int) -> int:
    """Exact ``int8q`` payload size for a chunk of ``raw_nbytes`` — known
    *before* encoding, so the encode budget can reserve the encoded
    footprint up front (once per chunk, not once per pass)."""
    n_rows = -(-raw_nbytes // INT8_ROW_BYTES)
    return _INT8_HEADER.size + n_rows * 4 + n_rows * INT8_ROW_ELEMS


# --------------------------------------------------------------------- int8q

def _pad_rows(x: np.ndarray) -> np.ndarray:
    """Pad an (R, 256) fp32 block to the kernel's row-tile multiple."""
    pad = (-x.shape[0]) % _KERNEL_ROW_TILE
    if pad:
        x = np.concatenate([x, np.zeros((pad, INT8_ROW_ELEMS), np.float32)])
    return x


def encode_int8_block(raw: np.ndarray, with_digest: bool = False):
    """Quantize one chunk of raw fp32 bytes into an ``int8q`` payload.

    One fused pass: returns ``(payload, digest)`` where ``digest`` is the
    checksum of the packed payload (or ``None`` when ``with_digest`` is
    off). ``raw`` is a uint8 view of the chunk's raw bytes; its length need
    not be a multiple of a row (the tensor tail) — the pad is zeros, which
    quantize exactly and are truncated by :func:`decode_int8_block`.
    """
    from repro.kernels import ops as kops  # deferred: jax import is heavy
    from repro.kernels import ref as kref

    raw = np.ascontiguousarray(raw, dtype=np.uint8)
    raw_nbytes = raw.nbytes
    pad = (-raw_nbytes) % INT8_ROW_BYTES
    if pad:
        raw = np.concatenate([raw, np.zeros(pad, np.uint8)])
    f32 = raw.view(np.float32).reshape(-1, INT8_ROW_ELEMS)
    n_rows = f32.shape[0]
    digest = None
    if kops.host_fastpath():
        if with_digest:
            q, scales, area = kref.fused_quantize_checksum_ref(
                _pad_rows(f32), n_rows)
            digest = (_header_digest(n_rows, raw_nbytes) + area) & 0xFFFFFFFF
        else:
            q, scales = kref.quantize_int8_ref(_pad_rows(f32))
    else:
        q, scales, area = kops.on_host(
            kops.fused_quantize_int8, _pad_rows(f32), n_rows=n_rows,
            nbytes=raw_nbytes)
        if with_digest:
            digest = (_header_digest(n_rows, raw_nbytes) + int(area)) \
                & 0xFFFFFFFF
    q = np.asarray(q)[:n_rows]
    scales = np.asarray(scales)[:n_rows]
    payload = (_INT8_HEADER.pack(n_rows, raw_nbytes)
               + scales.astype(np.float32).tobytes()
               + q.astype(np.int8).tobytes())
    return payload, digest


def decode_int8_block(payload: bytes, raw_lo: int, raw_hi: int,
                      expect_digest=None) -> np.ndarray:
    """Inverse of :func:`encode_int8_block`: dequantized raw bytes of
    ``[raw_lo, raw_hi)`` as a fresh uint8 array of length ``raw_hi-raw_lo``.
    Lossy-bounded: each fp32 value is within one quantization step
    (``row max|x| / 127``) of the original. With ``expect_digest`` the
    payload is integrity-verified while decoding (fused on TPU)."""
    from repro.kernels import ops as kops  # deferred: jax import is heavy
    from repro.kernels import ref as kref

    if len(payload) < _INT8_HEADER.size:
        raise CodecError("int8q payload shorter than its header")
    n_rows, raw_nbytes = _INT8_HEADER.unpack_from(payload)
    if raw_nbytes != raw_hi - raw_lo:
        raise CodecError(
            f"int8q payload declares {raw_nbytes} raw bytes, chunk "
            f"addressing says [{raw_lo}:{raw_hi}) — corrupt payload")
    want = _INT8_HEADER.size + n_rows * 4 + n_rows * INT8_ROW_ELEMS
    if len(payload) != want:
        raise CodecError(
            f"int8q payload is {len(payload)} B, expected {want} B for "
            f"{n_rows} rows — truncated or corrupt")
    off = _INT8_HEADER.size
    scales = np.frombuffer(payload, np.float32, n_rows, off).reshape(-1, 1)
    q = np.frombuffer(payload, np.int8, n_rows * INT8_ROW_ELEMS,
                      off + n_rows * 4).reshape(-1, INT8_ROW_ELEMS)
    pad = (-n_rows) % _KERNEL_ROW_TILE
    qp, sp = q, scales
    if pad:
        qp = np.concatenate([q, np.zeros((pad, INT8_ROW_ELEMS), np.int8)])
        sp = np.concatenate([scales, np.ones((pad, 1), np.float32)])
    if kops.host_fastpath():
        if expect_digest is not None:
            got = (_header_digest(n_rows, raw_nbytes)
                   + kref.int8_payload_digest_ref(q, scales, n_rows)) \
                & 0xFFFFFFFF
            if got != expect_digest:
                raise CodecError(
                    f"int8q payload digest mismatch: stored "
                    f"{expect_digest:#010x}, decoded {got:#010x} — "
                    f"corrupt chunk")
        # q(int8) -> f32 multiply is exactly rounded: bit-identical to the
        # dequantize kernel on any backend
        deq = qp.astype(np.float32)[:n_rows] * scales
    else:
        deq, area = kops.on_host(kops.fused_dequantize_int8, qp, sp,
                                 n_rows=n_rows, nbytes=raw_nbytes)
        if expect_digest is not None:
            got = (_header_digest(n_rows, raw_nbytes) + int(area)) \
                & 0xFFFFFFFF
            if got != expect_digest:
                raise CodecError(
                    f"int8q payload digest mismatch: stored "
                    f"{expect_digest:#010x}, decoded {got:#010x} — "
                    f"corrupt chunk")
        deq = deq[:n_rows]
    out = deq.astype(np.float32).reshape(-1).view(np.uint8)
    return np.array(out[:raw_nbytes])


# --------------------------------------------------------------------- delta

def _u32_words(b: np.ndarray) -> np.ndarray:
    """Flat u32 view of a byte array (zero-padded tail, alignment-safe)."""
    b = b.reshape(-1).view(np.uint8)
    pad = (-b.size) % 4
    if pad:
        b = np.concatenate([b, np.zeros(pad, np.uint8)])
    if not b.flags["C_CONTIGUOUS"] or b.ctypes.data % 4:
        b = b.copy()
    return b.view(np.uint32)


def encode_delta_chunk(cur: np.ndarray, prev: np.ndarray,
                       with_digest: bool = False):
    """XOR-delta one chunk: ``(delta_bytes_u8, digest|None)`` in one pass.

    ``cur`` (the staged bytes) is read exactly once; the digest covers the
    delta payload as stored (computed from the XOR output, which on TPU
    never leaves the kernel's VMEM tile)."""
    from repro.kernels import ops as kops  # deferred: jax import is heavy
    from repro.kernels import ref as kref

    nbytes = cur.nbytes
    digest = None
    if kops.host_fastpath():
        if with_digest:
            delta, digest = kref.fused_xor_checksum_ref(
                _u32_words(cur), _u32_words(prev))
        else:
            delta = np.bitwise_xor(_u32_words(cur), _u32_words(prev))
    else:
        delta, dig = kops.on_host(kops.fused_xor_checksum, cur, prev)
        if with_digest:
            digest = int(dig)
    return delta.view(np.uint8)[:nbytes], digest


# ------------------------------------------------------------------ registry

#: self-contained decoders:
#: codec base → fn(payload, raw_lo, raw_hi, expect_digest) → u8.
_DECODERS: Dict[str, Callable[..., np.ndarray]] = {
    "int8q": decode_int8_block,
}


def decode_chunk_payload(codec: str, payload: bytes,
                         raw_lo: int, raw_hi: int,
                         expect_digest=None) -> np.ndarray:
    """Decode one decompressed encoded-chunk payload back to raw bytes.

    Only valid for self-contained codecs; chained codecs (XOR deltas) must
    go through chain replay instead. ``expect_digest`` (from the footer's
    per-chunk record) makes the decode integrity-verifying."""
    if is_chained_codec(codec):
        raise CodecError(
            f"codec {codec!r} is chained (differential) — its payloads "
            f"only decode during chain replay, not standalone")
    fn = _DECODERS.get(codec_base(codec))
    if fn is None:
        raise CodecError(f"unknown tensor chunk codec {codec!r}")
    return fn(payload, raw_lo, raw_hi, expect_digest)
