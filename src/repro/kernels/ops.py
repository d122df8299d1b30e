"""jit'd public wrappers around the Pallas kernels.

This module alone decides ``interpret`` for the kernels (each kernel module
requires it as a keyword): compiled on a TPU backend, interpreted elsewhere
unless the caller passes it. The kernels themselves are written for the TPU
target (BlockSpec VMEM tiling, SMEM digest scalars, MXU-shaped dots).
"""

from __future__ import annotations

import functools
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs

from . import checksum as _checksum
from . import delta as _delta
from . import flash_attention as _fa
from . import fused as _fused
from . import quantize as _quant
from . import ref as _ref


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def host_fastpath() -> bool:
    """True when there is no real TPU backend.

    Interpret-mode Pallas is a correctness harness, not a data path (it
    moves tens of MB/s); without a TPU the encode/verify hot paths dispatch
    to the pure-NumPy oracles in ``ref.py``, which the differential suite
    (``tests/test_fused_kernels.py``) proves bit-identical to the kernels.
    """
    return _default_interpret()


def on_host(kernel, *args, nbytes: int | None = None, **static):
    """``kernel(*args, **static)`` on host arrays, its outputs back as NumPy
    arrays: the device round trip of every encode and verify path that runs
    a kernel on bytes already on the host.

    Traced, the round trip is three spans, each with ``bytes`` (``nbytes``,
    else the first input's) and ``kernel`` (the jitted function's name):
    ``kernel.h2d``, an explicit ``device_put`` of the inputs;
    ``kernel.call``, closed once the device has finished; ``kernel.d2h``,
    the outputs copied back. The waits for the first two only move: the
    copy back waits for both. Device inputs key the jit cache apart from
    NumPy ones, so a traced process traces each input shape once more
    (the compiled program is reused)."""
    if not obs.enabled():
        return jax.tree.map(np.asarray, kernel(*args, **static))
    attrs = {"kernel": kernel.__name__,
             "bytes": args[0].nbytes if nbytes is None else nbytes}
    t0 = time.perf_counter()
    args = jax.block_until_ready(jax.device_put(args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(kernel(*args, **static))
    t2 = time.perf_counter()
    out = jax.tree.map(np.asarray, out)
    obs.add_span("kernel.h2d", t0, t1, **attrs)
    obs.add_span("kernel.call", t1, t2, **attrs)
    obs.add_span("kernel.d2h", t2, time.perf_counter(), **attrs)
    return out


def tensor_checksum_fast(x) -> int:
    """``tensor_checksum`` as a Python int, via the fastest bit-exact path."""
    if host_fastpath():
        return _ref.checksum_np_bytes(np.asarray(x))
    return int(on_host(tensor_checksum, x))


def _pad_to(x: jax.Array, multiple: int) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.pad(x, (0, pad))
    return x


def as_u32(x) -> jax.Array:
    """Flat little-endian uint32 words of any array's bytes (zero-padding
    the byte tail).

    4-byte dtypes bitcast in place. Narrower ones pack their elements into
    words from strided 1-D slices: a bitcast through an (n, 4) or (n, 2)
    shape would put that tiny minor dim on the TPU's 128-lane tiles and
    grow the array 64-128x in HBM."""
    x = jnp.asarray(x).reshape(-1)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    size = x.dtype.itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if size == 8:
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    per = 4 // size
    u = _pad_to(jax.lax.bitcast_convert_type(
        x, {1: jnp.uint8, 2: jnp.uint16}[size]), per)
    words = u[0::per].astype(jnp.uint32)
    for i in range(1, per):
        words = words | jnp.left_shift(u[i::per].astype(jnp.uint32),
                                       jnp.uint32(8 * size * i))
    return words


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def tensor_checksum(x, block: int = _checksum.BLOCK,
                    interpret: bool | None = None) -> jax.Array:
    """Position-weighted u32 checksum of any array's bytes."""
    interp = _default_interpret() if interpret is None else interpret
    u = _pad_to(as_u32(x), block)
    return _checksum.checksum_u32(u, block=block, interpret=interp)


@functools.partial(jax.jit, static_argnames=("interpret",))
def downcast_bf16(x, interpret: bool | None = None) -> jax.Array:
    interp = _default_interpret() if interpret is None else interpret
    return _quant.downcast_bf16(x, interpret=interp)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int8(x, interpret: bool | None = None):
    interp = _default_interpret() if interpret is None else interpret
    return _quant.quantize_int8(x, interpret=interp)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_int8(q, scales, interpret: bool | None = None) -> jax.Array:
    interp = _default_interpret() if interpret is None else interpret
    return _quant.dequantize_int8(q, scales, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def delta_xor(cur, prev, block: int = _delta.BLOCK,
              interpret: bool | None = None) -> jax.Array:
    interp = _default_interpret() if interpret is None else interpret
    c = _pad_to(as_u32(cur), block)
    p = _pad_to(as_u32(prev), block)
    return _delta.delta_xor(c, p, block=block, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def delta_f32(cur, prev, block: int = _delta.BLOCK,
              interpret: bool | None = None) -> jax.Array:
    interp = _default_interpret() if interpret is None else interpret
    c = _pad_to(jnp.asarray(cur, jnp.float32).reshape(-1), block)
    p = _pad_to(jnp.asarray(prev, jnp.float32).reshape(-1), block)
    return _delta.delta_f32(c, p, block=block, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_xor_checksum(cur, prev, block: int = _delta.BLOCK,
                       interpret: bool | None = None):
    """One-pass (delta, digest-of-delta) over any two same-size arrays."""
    interp = _default_interpret() if interpret is None else interpret
    c = _pad_to(as_u32(cur), block)
    p = _pad_to(as_u32(prev), block)
    return _fused.xor_checksum_u32(c, p, block=block, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_xor_fold(base, delta, block: int = _delta.BLOCK,
                   interpret: bool | None = None):
    """One-pass (base ^ delta, digest-of-delta): fused chain-replay decode."""
    interp = _default_interpret() if interpret is None else interpret
    b = _pad_to(as_u32(base), block)
    d = _pad_to(as_u32(delta), block)
    return _fused.xor_fold_checksum_u32(b, d, block=block, interpret=interp)


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def fused_quantize_int8(x, n_rows: int, interpret: bool | None = None):
    """One-pass (q, scales, int8q payload digest); x: (R, 256) fp32."""
    interp = _default_interpret() if interpret is None else interpret
    return _fused.quantize_checksum_int8(x, n_rows, interpret=interp)


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def fused_dequantize_int8(q, scales, n_rows: int,
                          interpret: bool | None = None):
    """One-pass (fp32, int8q payload digest): fused int8 decode + verify."""
    interp = _default_interpret() if interpret is None else interpret
    return _fused.dequantize_checksum_int8(q, scales, n_rows,
                                           interpret=interp)


@functools.partial(jax.jit, static_argnames=(
    "kind", "window", "chunk", "q_block", "kv_block", "interpret"))
def flash_attention(q, k, v, kind: str = "full", window: int = 0,
                    chunk: int = 0, q_block: int = _fa.Q_BLOCK,
                    kv_block: int = _fa.KV_BLOCK,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, S, H, hd); k/v: (B, T, KV, hd). GQA folded into heads."""
    interp = _default_interpret() if interpret is None else interpret
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    rep = H // KV
    kr = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vr = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = kr.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vf = vr.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    out = _fa.flash_attention_bh(qf, kf, vf, kind=kind, window=window,
                                 chunk=chunk, q_block=q_block,
                                 kv_block=kv_block, interpret=interp)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
