"""Blocked integrity checksum kernel (Pallas TPU).

Computes a position-weighted modular checksum over a flat u32 view of a
tensor shard: ``sum_i (x_i * (a + i mod M)) mod 2^32``. Position weighting
catches reordered blocks, which a plain sum would miss. The grid walks
VMEM-sized blocks of the flattened input; each step accumulates into a (1,1)
SMEM-resident partial in the output ref (grid iterations on TPU are
sequential, so the accumulation is race-free).

Mosaic has no reductions over unsigned integers and no scalar bitcasts, so
partials are summed and accumulated as int32 and the result is bitcast to
uint32 outside the kernel: two's-complement addition wraps to the same bits
as uint32 addition mod 2^32, so the digest stays bit-identical to
``ref.checksum_np``.

VMEM budget: BLOCK u32 elements (default 64k = 256 KiB) — comfortably inside
the ~16 MiB/core VMEM with room for double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 65_536          # u32 elements per grid step (256 KiB VMEM)
WEIGHT_MOD = 65_521     # largest prime < 2^16 (adler-style)
WEIGHT_BASE = 65_599


def wrapping_sum_i32(x_u32: jax.Array) -> jax.Array:
    """Sum of uint32 values mod 2^32, as the int32 with the same bits."""
    return jnp.sum(jax.lax.bitcast_convert_type(x_u32, jnp.int32))


def digest_spec() -> pl.BlockSpec:
    """The (1, 1) int32 digest accumulator: a scalar, so it lives in SMEM
    (Mosaic cannot store scalars to VMEM)."""
    return pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)


def accumulate_digest(dig_ref, partial_i32) -> None:
    """Add one grid step's partial into the SMEM accumulator (zeroed at step
    0; grid steps run in order, so this is race-free)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dig_ref[0, 0] = jnp.int32(0)

    dig_ref[0, 0] = dig_ref[0, 0] + partial_i32


def digest_u32(dig: jax.Array) -> jax.Array:
    """The uint32 digest held in a kernel's (1, 1) int32 accumulator."""
    return jax.lax.bitcast_convert_type(dig[0, 0], jnp.uint32)


def _checksum_kernel(x_ref, out_ref):
    step = pl.program_id(0)
    x = x_ref[...].astype(jnp.uint32)
    n = x.shape[0]
    idx = (jax.lax.iota(jnp.uint32, n)
           + jnp.uint32(step) * jnp.uint32(n))
    w = jnp.uint32(WEIGHT_BASE) + (idx % jnp.uint32(WEIGHT_MOD))
    accumulate_digest(out_ref, wrapping_sum_i32(x * w))


def checksum_u32(x_flat_u32: jax.Array, *, interpret: bool,
                 block: int = BLOCK) -> jax.Array:
    """x_flat_u32: 1-D uint32 (pre-padded to a multiple of ``block``)."""
    n = x_flat_u32.shape[0]
    assert n % block == 0, f"pad input to a multiple of {block}"
    grid = (n // block,)
    dig = pl.pallas_call(
        _checksum_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=digest_spec(),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret,
    )(x_flat_u32)
    return digest_u32(dig)
