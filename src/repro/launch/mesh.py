"""Production mesh construction.

Defined as functions (not module-level constants) so importing this module
never touches JAX device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any JAX
import; everything else sees the real device count.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax


def make_mesh(dims: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler, as the partition rules assume)."""
    return jax.make_mesh(tuple(dims), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU training)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
