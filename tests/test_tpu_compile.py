"""Compile every kernel on ``kernels.ops``' path for a TPU v5e, without one.

Interpret mode runs the kernels' Python, not Mosaic, so it cannot see what
the chip's compiler refuses (unsigned reductions, scalar stores to VMEM,
lane-padded reshapes that blow up HBM). These tests hand the installed TPU
compiler a described ``v5e:2x2`` topology and compile each wrapper with
``interpret=False`` at the chunk widths the engine uses. Nothing runs.

The topology is described only inside a fixture: loading the TPU library is
exclusive to one process, and every test worker imports this module.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

WORDS = 16 * 65_536          # one 4 MiB engine chunk as uint32 words
CHUNK_BYTES = 4 * WORDS
ROWS = 1024                  # int8 route: 1024 rows of 256 fp32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _u32(s):
    return jax.ShapeDtypeStruct((WORDS,), jnp.uint32, sharding=s)


def _u8(s):
    return jax.ShapeDtypeStruct((CHUNK_BYTES,), jnp.uint8, sharding=s)


def _rows(s, dtype, cols=256):
    return jax.ShapeDtypeStruct((ROWS, cols), dtype, sharding=s)


# name -> (wrapper with interpret=False, argument shapes for a sharding)
CASES = {
    "tensor_checksum": (
        lambda x: ops.tensor_checksum(x, interpret=False),
        lambda s: [_u32(s)]),
    "tensor_checksum_bytes": (
        lambda x: ops.tensor_checksum(x, interpret=False),
        lambda s: [_u8(s)]),
    "fused_xor_checksum": (
        lambda a, b: ops.fused_xor_checksum(a, b, interpret=False),
        lambda s: [_u8(s), _u8(s)]),
    "fused_xor_fold": (
        lambda a, b: ops.fused_xor_fold(a, b, interpret=False),
        lambda s: [_u32(s), _u32(s)]),
    "fused_quantize_int8": (
        lambda x: ops.fused_quantize_int8(x, ROWS - 3, interpret=False),
        lambda s: [_rows(s, jnp.float32)]),
    "fused_dequantize_int8": (
        lambda q, sc: ops.fused_dequantize_int8(q, sc, ROWS - 3,
                                                interpret=False),
        lambda s: [_rows(s, jnp.int8), _rows(s, jnp.float32, 1)]),
    "delta_xor": (
        lambda a, b: ops.delta_xor(a, b, interpret=False),
        lambda s: [_u8(s), _u8(s)]),
    "delta_f32": (
        lambda a, b: ops.delta_f32(a, b, interpret=False),
        lambda s: [jax.ShapeDtypeStruct((WORDS,), jnp.float32,
                                        sharding=s)] * 2),
    "downcast_bf16": (
        lambda x: ops.downcast_bf16(x, interpret=False),
        lambda s: [_rows(s, jnp.float32)]),
    "quantize_int8": (
        lambda x: ops.quantize_int8(x, interpret=False),
        lambda s: [_rows(s, jnp.float32)]),
    "dequantize_int8": (
        lambda q, sc: ops.dequantize_int8(q, sc, interpret=False),
        lambda s: [_rows(s, jnp.int8), _rows(s, jnp.float32, 1)]),
    "flash_attention": (
        lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
        lambda s: [jax.ShapeDtypeStruct((1, 512, 8, 64), jnp.bfloat16,
                                        sharding=s)]
        + [jax.ShapeDtypeStruct((1, 512, 2, 64), jnp.bfloat16,
                                sharding=s)] * 2),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = shapes(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not XLA
    # byte inputs are packed into words without lane padding: a bitcast
    # through (n, 4) would need ~32x the chunk in HBM
    mem = compiled.memory_analysis()
    arg_bytes = sum(a.size * a.dtype.itemsize for a in args)
    assert mem.temp_size_in_bytes <= 4 * arg_bytes, mem
