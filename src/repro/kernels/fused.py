"""One-pass fused encode/decode kernels (Pallas TPU).

The encode lane used to walk every staged chunk once per codec stage:
``delta.py`` XORed, ``quantize.py`` quantized, ``checksum.py`` hashed — three
kernel launches, three reads of bytes that are immutable for the whole save.
These kernels collapse each encoded route into a single pallas_call per chunk
that reads the staged bytes exactly once and emits the encoded payload *and*
its integrity digest together:

* ``xor_checksum_u32``       — delta = cur ^ prev, plus the position-weighted
  checksum of the delta words (the stored payload), in one pass over cur.
* ``xor_fold_checksum_u32``  — the symmetric decode: folded = base ^ delta,
  plus the checksum of the *incoming* delta words, so chain replay verifies
  each payload while applying it (one read of the delta).
* ``quantize_checksum_int8`` — per-row int8 quantization (same math as
  ``quantize.py``) plus the checksum of the packed ``int8q`` payload area the
  kernel produces (scale words + little-endian-packed q words at their final
  payload word positions); the 8-byte header's contribution is two scalar
  terms added host-side.
* ``dequantize_checksum_int8`` — the symmetric decode: dequantize and digest
  the payload in one read of the q words.

Digest convention: every digest is the ``checksum.py`` position-weighted
modular sum over the uncompressed payload's little-endian u32 words —
``sum_i payload_u32[i] * (BASE + i mod M) mod 2^32`` — so a fused digest is
bit-identical to ``checksum_u32`` over the packed payload bytes. Zero words
contribute zero, which makes block padding (and the zero-padded q rows of a
partial tile) digest-neutral; padded *scale* rows are not in the payload and
are masked out explicitly.

Grid iterations on TPU are sequential, so the (1,1) SMEM digest accumulator
in the output ref is race-free; it is int32 and reduced with wrapping, then
bitcast to uint32 outside the kernel (same idiom as ``checksum.py``). VMEM
budget per grid step: one 256 KiB u32 slab per input for the XOR kernels; a
(256, 256) fp32 tile + int8/scale outputs for the quantize kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .checksum import (BLOCK, WEIGHT_BASE, WEIGHT_MOD, accumulate_digest,
                       digest_spec, digest_u32, wrapping_sum_i32)
from .quantize import COLS, ROWS

# int8q payload layout (see core/codecs.py): u32 n_rows | u32 raw_nbytes |
# f32 scales[n_rows] | i8 q[n_rows * 256]. Word indices below are positions
# within that payload's u32 view.
PAYLOAD_HEADER_WORDS = 2


def _weights(idx_u32):
    return jnp.uint32(WEIGHT_BASE) + (idx_u32 % jnp.uint32(WEIGHT_MOD))


# --------------------------------------------------------------- delta route
def _xor_checksum_kernel(a_ref, b_ref, o_ref, dig_ref):
    step = pl.program_id(0)
    delta = jax.lax.bitwise_xor(a_ref[...], b_ref[...])
    o_ref[...] = delta
    n = delta.shape[0]
    idx = jax.lax.iota(jnp.uint32, n) + jnp.uint32(step) * jnp.uint32(n)
    accumulate_digest(dig_ref, wrapping_sum_i32(delta * _weights(idx)))


def xor_checksum_u32(cur_u32: jax.Array, prev_u32: jax.Array, *,
                     interpret: bool, block: int = BLOCK):
    """(delta, digest-of-delta) in one read of ``cur``/``prev``."""
    n = cur_u32.shape[0]
    assert n % block == 0 and cur_u32.shape == prev_u32.shape
    grid = (n // block,)
    delta, dig = pl.pallas_call(
        _xor_checksum_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                   digest_spec()],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.uint32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(cur_u32, prev_u32)
    return delta, digest_u32(dig)


def _xor_fold_checksum_kernel(base_ref, d_ref, o_ref, dig_ref):
    step = pl.program_id(0)
    delta = d_ref[...]
    o_ref[...] = jax.lax.bitwise_xor(base_ref[...], delta)
    n = delta.shape[0]
    idx = jax.lax.iota(jnp.uint32, n) + jnp.uint32(step) * jnp.uint32(n)
    accumulate_digest(dig_ref, wrapping_sum_i32(delta * _weights(idx)))


def xor_fold_checksum_u32(base_u32: jax.Array, delta_u32: jax.Array, *,
                          interpret: bool, block: int = BLOCK):
    """(base ^ delta, digest-of-delta): verify the payload while applying."""
    n = base_u32.shape[0]
    assert n % block == 0 and base_u32.shape == delta_u32.shape
    grid = (n // block,)
    folded, dig = pl.pallas_call(
        _xor_fold_checksum_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                   digest_spec()],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.uint32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(base_u32, delta_u32)
    return folded, digest_u32(dig)


# ------------------------------------------------------------ int8q route
def _payload_digest_tile(q, scale, n_rows: int):
    """Digest contribution (int32 bits) of one (ROWS, COLS) tile's payload.

    q words: 4 consecutive int8 lanes pack little-endian into the u32 word
    at payload index ``2 + n_rows + row * COLS//4 + col//4``. Since the
    digest is linear mod 2^32, each byte is weighted in place as
    ``(u8 << 8 * (col % 4)) * weight(word index)`` — no lane reshape.
    Zero-padded rows quantize to q == 0 and contribute nothing. Scale words
    sit at payload index ``2 + row`` and exist only for live rows (padding
    is masked).
    """
    step = pl.program_id(0)
    rows, cols = q.shape
    words_per_row = cols // 4
    row0 = jnp.uint32(step) * jnp.uint32(rows)
    qu = jax.lax.bitcast_convert_type(q.astype(jnp.int32) & 0xFF, jnp.uint32)
    r_iota = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    q_idx = (jnp.uint32(PAYLOAD_HEADER_WORDS + n_rows)
             + (row0 + r_iota) * jnp.uint32(words_per_row)
             + jnp.right_shift(c_iota, jnp.uint32(2)))
    shift = jnp.left_shift(c_iota & jnp.uint32(3), jnp.uint32(3))
    q_term = jnp.left_shift(qu, shift) * _weights(q_idx)

    sbits = jax.lax.bitcast_convert_type(scale, jnp.uint32)       # (rows, 1)
    s_rows = row0 + jax.lax.broadcasted_iota(jnp.uint32, (rows, 1), 0)
    live = s_rows < jnp.uint32(n_rows)
    s_term = jnp.where(live,
                       sbits * _weights(jnp.uint32(PAYLOAD_HEADER_WORDS)
                                        + s_rows),
                       jnp.uint32(0))
    return wrapping_sum_i32(q_term) + wrapping_sum_i32(s_term)


def _quant_checksum_kernel(x_ref, q_ref, s_ref, dig_ref, *, n_rows: int):
    x = x_ref[...]
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = scale
    accumulate_digest(dig_ref, _payload_digest_tile(q, scale, n_rows))


def quantize_checksum_int8(x: jax.Array, n_rows: int, *,
                           interpret: bool):
    """x: (R, COLS) fp32, R % ROWS == 0 -> (q, scales, payload digest).

    ``n_rows`` is the live (un-padded) row count; the digest covers exactly
    the scale + q payload words of those rows (header words are host-side).
    """
    R, C = x.shape
    assert R % ROWS == 0 and C == COLS and 0 < n_rows <= R
    grid = (R // ROWS,)
    kern = functools.partial(_quant_checksum_kernel, n_rows=n_rows)
    q, scales, dig = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
                   pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
                   digest_spec()],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(x)
    return q, scales, digest_u32(dig)


def _dequant_checksum_kernel(q_ref, s_ref, o_ref, dig_ref, *, n_rows: int):
    q = q_ref[...]
    scale = s_ref[...]
    o_ref[...] = q.astype(jnp.float32) * scale
    accumulate_digest(dig_ref, _payload_digest_tile(q, scale, n_rows))


def dequantize_checksum_int8(q: jax.Array, scales: jax.Array, n_rows: int, *,
                             interpret: bool):
    """Symmetric decode: (fp32, payload digest) in one read of q/scales."""
    R, C = q.shape
    assert R % ROWS == 0 and C == COLS and 0 < n_rows <= R
    grid = (R // ROWS,)
    kern = functools.partial(_dequant_checksum_kernel, n_rows=n_rows)
    out, dig = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
                  pl.BlockSpec((ROWS, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
                   digest_spec()],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(q, scales)
    return out, digest_u32(dig)
