"""Per-leaf fingerprints of a state's bytes, computed on the device.

Two wrapping uint32 sums of each leaf's words, one of them weighted by odd
position factors: a changed, lost or moved word changes a row. The check
compares the fingerprint of every state a checkpoint captured with the
fingerprint of what the program reads back.
"""

from __future__ import annotations

from typing import Any, List

import jax
import jax.numpy as jnp
import numpy as np

_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _words(x) -> jax.Array:
    x = jnp.asarray(x).reshape(-1)
    size = x.dtype.itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(x, _UINT[size]).astype(jnp.uint32)


@jax.jit
def _rows(leaves: List[jax.Array]) -> jax.Array:
    rows = []
    for x in leaves:
        w = _words(x)
        weight = jax.lax.iota(jnp.uint32, w.shape[0]) * jnp.uint32(2) \
            + jnp.uint32(1)
        rows.append(jnp.stack([jnp.sum(w), jnp.sum(w * weight)]))
    return jnp.stack(rows)


def array_leaves(tree: Any) -> List[jax.Array]:
    return [x for x in jax.tree_util.tree_leaves(tree)
            if isinstance(x, jax.Array)]


def fingerprint(tree: Any) -> jax.Array:
    """(leaves, 2) uint32 on the device; dispatched, not waited for."""
    return _rows(array_leaves(tree))


def mismatches(got, want) -> int:
    """Rows that differ; a different number of leaves counts them all."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return max(len(got), len(want))
    return int(np.any(got != want, axis=1).sum())
