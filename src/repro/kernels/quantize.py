"""Quantize-pack kernels for compressed checkpoints (Pallas TPU).

Two codecs used by the differential/compressed checkpoint path (the paper's
stated future work, implemented here as a beyond-paper feature):

* fp32 → bf16 downcast (2× smaller optimizer-state snapshots);
* fp32 → int8 blockwise symmetric quantization: each (ROWS, COLS) tile gets a
  per-row scale = max|x|/127 and values round to int8 (4× smaller).

Tiles are (256, 256) fp32 = 256 KiB in / 64-128 KiB out per grid step —
VMEM-friendly, lane-dim 256 = 2×128 (hardware-aligned).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 256
COLS = 256


def _downcast_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(jnp.bfloat16)


def downcast_bf16(x: jax.Array, *, interpret: bool) -> jax.Array:
    """x: (R, C) fp32, R % ROWS == 0, C % COLS == 0 -> (R, C) bf16."""
    R, C = x.shape
    assert R % ROWS == 0 and C % COLS == 0
    grid = (R // ROWS, C // COLS)
    return pl.pallas_call(
        _downcast_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, COLS), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((ROWS, COLS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.bfloat16),
        interpret=interpret,
    )(x)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = scale


def quantize_int8(x: jax.Array, *, interpret: bool):
    """x: (R, C) fp32 -> (int8 (R, C), scales (R, 1) fp32), per-row symmetric."""
    R, C = x.shape
    assert R % ROWS == 0 and C % COLS == 0 and C == COLS, \
        "per-row scales require a single column tile"
    grid = (R // ROWS,)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
                   pl.BlockSpec((ROWS, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        interpret=interpret,
    )(x)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def dequantize_int8(q: jax.Array, scales: jax.Array, *,
                    interpret: bool) -> jax.Array:
    R, C = q.shape
    grid = (R // ROWS,)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
                  pl.BlockSpec((ROWS, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=interpret,
    )(q, scales)
