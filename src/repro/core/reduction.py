"""Checkpoint data reduction (beyond-paper: the paper's §VII future work).

Codecs applied per tensor *on device* (Pallas kernels) before host
compression:

* ``bf16``   — fp32→bf16 downcast of optimizer moments (2×, lossy-bounded);
* ``int8``   — blockwise symmetric quantization (4×, lossy-bounded);
* ``delta``  — XOR vs the previous snapshot (lossless) — slowly-moving
  state XORs to sparse bitstreams that zstd crushes;
* ``zstd``   — host-side entropy coding (always applied last).

``DifferentialCheckpointer`` keeps the previous snapshot per tensor and
writes either a keyframe (full) or a delta, with integrity checksums from
``kernels.ops.tensor_checksum``. Restore replays keyframe ⊕ deltas.

NOTE: differential checkpointing is now a first-class *engine* path —
``CheckpointManager(..., delta=DeltaPolicy())`` streams XOR deltas through
the async data-movement engine with chain-aware catalog/GC/verify and
parallel chain restore. The synchronous ``DifferentialCheckpointer`` here
is deprecated for training use (see its docstring)."""

from __future__ import annotations

import dataclasses
import io
import os
import pickle
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import zlib

import zstandard

from repro.kernels import ops as kops


@dataclasses.dataclass
class EncodedTensor:
    codec: str                  # "raw" | "delta-xor"
    quant: str                  # "none" | "bf16" | "int8"
    payload: bytes              # zstd-compressed
    dtype: str
    shape: Tuple[int, ...]
    checksum: int               # of the *original* bytes
    raw_nbytes: int
    scales: Optional[bytes] = None


def _compress(b: bytes, level: int = 3) -> bytes:
    return zstandard.ZstdCompressor(level=level).compress(b)


_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"


def _decompress(b: bytes) -> bytes:
    # Files written by installs without zstandard hold zlib frames: sniff
    # the frame so they still read.
    if b[:4] == _ZSTD_MAGIC:
        return zstandard.ZstdDecompressor().decompress(b)
    return zlib.decompress(b)


def encode_tensor(arr: jax.Array, *, prev: Optional[np.ndarray] = None,
                  quant: str = "none") -> Tuple[EncodedTensor, np.ndarray]:
    """Encode one tensor: optional on-device quantize, optional XOR delta
    against ``prev`` (same quantized domain), then zstd. Returns the
    encoded record *and* the working-precision array (the ``prev`` to
    retain for the next delta)."""
    checksum = int(kops.tensor_checksum(arr))
    dtype, shape = str(arr.dtype), tuple(arr.shape)
    scales = None
    if quant == "bf16" and arr.dtype == jnp.float32 and arr.ndim == 2 \
            and arr.shape[0] % 256 == 0 and arr.shape[1] % 256 == 0:
        work = np.asarray(kops.downcast_bf16(arr))
    elif quant == "int8" and arr.dtype == jnp.float32 and arr.ndim == 2 \
            and arr.shape[0] % 256 == 0 and arr.shape[1] == 256:
        q, s = kops.quantize_int8(arr)
        work = np.asarray(q)
        scales = _compress(np.asarray(s).tobytes())
    else:
        quant = "none"
        work = np.asarray(arr)
    if prev is not None and prev.shape == work.shape \
            and prev.dtype == work.dtype:
        delta = np.asarray(kops.delta_xor(jnp.asarray(work),
                                          jnp.asarray(prev)))
        payload = _compress(delta.tobytes())
        codec = "delta-xor"
    else:
        payload = _compress(np.ascontiguousarray(work).tobytes())
        codec = "raw"
    return EncodedTensor(codec=codec, quant=quant, payload=payload,
                         dtype=dtype, shape=shape, checksum=checksum,
                         raw_nbytes=int(np.asarray(arr).nbytes),
                         scales=scales), work


def decode_tensor(enc: EncodedTensor, *, prev: Optional[np.ndarray] = None
                  ) -> np.ndarray:
    """Inverse of encode (returns the *working-precision* array)."""
    raw = _decompress(enc.payload)
    if enc.codec == "delta-xor":
        assert prev is not None, "delta decode needs the previous snapshot"
        n_u32 = len(raw) // 4
        delta = np.frombuffer(raw, np.uint32)
        prev_u32 = prev.reshape(-1).view(np.uint8)
        pad = (-len(prev_u32)) % 4
        prev_u32 = np.pad(prev_u32, (0, pad)).view(np.uint32)
        pad2 = n_u32 - len(prev_u32)
        if pad2:
            prev_u32 = np.pad(prev_u32, (0, pad2))
        cur = np.bitwise_xor(delta, prev_u32)
        work = cur.view(np.uint8)
    else:
        work = np.frombuffer(raw, np.uint8)
    if enc.quant == "bf16":
        arr = work[:int(np.prod(enc.shape)) * 2].view(jnp.bfloat16)
    elif enc.quant == "int8":
        arr = work[:int(np.prod(enc.shape))].view(np.int8)
    else:
        arr = work[:enc.raw_nbytes].view(np.dtype(enc.dtype))
    return np.array(arr).reshape(enc.shape)


class DifferentialCheckpointer:
    """Keyframe + delta checkpoint stream for a pytree of arrays.

    .. deprecated::
        This standalone sidecar predates differential checkpointing on the
        main engine path and bypasses the async data-movement engine, the
        crash-consistent catalog, multi-rank coordination, and the parallel
        restore engine. Prefer ``CheckpointManager(..., delta=DeltaPolicy())``
        (see ``repro.core.checkpoint``): same keyframe+XOR-delta reduction,
        but lazy/async, chain-aware in GC/cascade/verify, and restored
        through ``RestoreEngine`` chain replay. This class remains for
        offline/sidecar use and as the reference for the quantized
        (``bf16``/``int8``) encode path.
    """

    def __init__(self, directory: str, *, keyframe_every: int = 4,
                 quant: str = "none"):
        self.directory = directory
        self.keyframe_every = keyframe_every
        self.quant = quant
        self._prev: Dict[str, np.ndarray] = {}
        self._n_saves = 0
        os.makedirs(directory, exist_ok=True)
        # Restart recovery: derive chain state from what is already on
        # disk. Without this, a restarted process had _n_saves=0 (→
        # keyframe cadence restarts) but ALSO wrote its first record with
        # keyframe=False whenever the cadence said "delta" — while
        # actually raw-encoding every tensor (_prev empty) — so restore()
        # across the restart failed its `chain[0]["keyframe"]` assertion.
        existing = self._existing_steps()
        if existing:
            self._n_saves = len(existing)
            try:
                # re-arm the delta bases from the last restorable step so
                # the chain continues across the restart
                self._prev = self.restore(existing[-1])
            except Exception:
                self._prev = {}  # damaged tail: next save re-keyframes

    def _existing_steps(self) -> List[int]:
        return sorted(int(f[5:13]) for f in os.listdir(self.directory)
                      if f.startswith("diff_") and f.endswith(".pkl"))

    def save(self, step: int, tree) -> Dict[str, Any]:
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        # no retained bases ⇒ this save is raw-encoded whatever the
        # cadence says; record it as the keyframe it actually is
        keyframe = (self._n_saves % self.keyframe_every == 0) \
            or not self._prev
        record: Dict[str, Any] = {"step": step, "keyframe": keyframe,
                                  "tensors": {}}
        raw_total = comp_total = 0
        for path, leaf in leaves:
            name = jax.tree_util.keystr(path)
            prev = None if keyframe else self._prev.get(name)
            enc, work = encode_tensor(jnp.asarray(leaf), prev=prev,
                                      quant=self.quant)
            self._prev[name] = work
            record["tensors"][name] = enc
            raw_total += enc.raw_nbytes
            comp_total += len(enc.payload)
        path = os.path.join(self.directory, f"diff_{step:08d}.pkl")
        # Deprecated standalone reducer (pre-repository legacy format): its
        # flat diff_*.pkl files live outside the catalog/manifest protocol
        # by definition; kept only for the migration window.
        with open(path, "wb") as f:  # ckptlint: disable=CKPT301
            pickle.dump(record, f, protocol=pickle.HIGHEST_PROTOCOL)
        self._n_saves += 1
        return {"path": path, "raw_bytes": raw_total,
                "compressed_bytes": comp_total,
                "ratio": raw_total / max(comp_total, 1),
                "keyframe": keyframe}

    def restore(self, step: int) -> Dict[str, np.ndarray]:
        """Replay keyframe + deltas up to ``step``."""
        files = sorted(os.listdir(self.directory))
        chain: List[Dict[str, Any]] = []
        for f in files:
            if not f.startswith("diff_"):
                continue
            s = int(f[5:13])
            if s > step:
                break
            try:
                with open(os.path.join(self.directory, f), "rb") as fh:
                    rec = pickle.load(fh)
            except Exception:
                # A broken link invalidates everything accumulated so far
                # — only a later keyframe can re-anchor the chain. Never
                # splice across a damaged record.
                chain = []
                continue
            if rec["keyframe"]:
                chain = [rec]
            else:
                chain.append(rec)
        assert chain and chain[0]["keyframe"], "no keyframe found"
        state: Dict[str, np.ndarray] = {}
        for rec in chain:
            for name, enc in rec["tensors"].items():
                state[name] = decode_tensor(enc, prev=state.get(name))
        return state
