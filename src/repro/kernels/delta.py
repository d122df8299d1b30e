"""Differential-checkpoint kernels (Pallas TPU).

Differential checkpointing (paper §VII future work): instead of persisting a
full snapshot every interval, persist ``delta = current - previous`` (exact
for integer/bit views via XOR) plus an occasional full keyframe. Deltas of
slowly-moving optimizer state are highly compressible downstream (zstd in the
host pipeline).

* ``delta_xor`` — bit-exact XOR of two u32 views (lossless, order-insensitive
  reconstruction: ``prev ^ delta = cur``).
* ``delta_f32`` — arithmetic difference of fp32 views (feeds the int8
  quantizer for lossy-but-bounded delta compression).

Tiles are 1-D BLOCK-element slabs (256 KiB VMEM per input).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 65_536


def _xor_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jax.lax.bitwise_xor(a_ref[...], b_ref[...])


def delta_xor(cur_u32: jax.Array, prev_u32: jax.Array, *,
              interpret: bool, block: int = BLOCK) -> jax.Array:
    n = cur_u32.shape[0]
    assert n % block == 0 and cur_u32.shape == prev_u32.shape
    grid = (n // block,)
    return pl.pallas_call(
        _xor_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
        interpret=interpret,
    )(cur_u32, prev_u32)


def _sub_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] - b_ref[...]


def delta_f32(cur: jax.Array, prev: jax.Array, *, interpret: bool,
              block: int = BLOCK) -> jax.Array:
    n = cur.shape[0]
    assert n % block == 0 and cur.shape == prev.shape
    grid = (n // block,)
    return pl.pallas_call(
        _sub_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
    )(cur, prev)
