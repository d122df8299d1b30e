"""Pallas TPU kernels for checkpoint-path compute hot-spots.

The paper's core contribution is I/O-side, so the checkpoint engine itself is
pure host/JAX code. These kernels implement the *device-side* compute the
paper defers to future work (integrity verification, data reduction for
checkpoints) plus the attention hot-spot of the model zoo:

* ``flash_attention`` — TPU twin of the pure-XLA blocked attention in
  ``repro.models.layers`` (MXU-tiled, VMEM-resident blocks).
* ``checksum`` — blocked integrity checksum over tensor shards, computed on
  device before staging so corrupted transfers are detectable.
* ``quantize`` — fp32→bf16/int8 quantize-pack for compressed checkpoints.
* ``delta`` — differential checkpointing: subtract/XOR vs previous snapshot.
* ``fused`` — the one-pass encode/decode pipeline: each encoded route
  (XOR delta, int8 quantize) emits its payload *and* integrity digest in a
  single kernel invocation per chunk, reading the staged bytes exactly once.

Each has a jit'd wrapper in :mod:`repro.kernels.ops` (compiled on a TPU,
interpreted elsewhere) and a pure-NumPy/jnp oracle in
:mod:`repro.kernels.ref`; tests sweep shapes/dtypes against the oracle, and
``tests/test_fused_kernels.py`` proves the fused kernels bit-identical to
the legacy multi-pass composition before the engine trusts either.
"""

from . import fused, ops, ref

__all__ = ["fused", "ops", "ref"]
