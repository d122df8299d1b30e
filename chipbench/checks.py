"""The numbers that decide ``correct``, and their limits.

Each number is a gap between what the program's timed path produced and
what the float32 reference computes from the same weights and tokens:

* ``loss_gap``: the largest absolute gap of a step's loss, over the first
  three steps (and, in a restore cell, every step run after a resume);
* ``grad_gap``: the worst leaf's gap between the norms of the first step's
  clipped gradient, as AdamW got it, over the larger of that leaf's and the
  median leaf's reference norm;
* ``change_gap``: the same for the norm of each leaf's change after three
  steps, leaving out leaves whose reference gradient is under a thousandth
  of the median leaf's (their moves are round-off, as a key's bias is under
  softmax);
* ``ckpt_mismatch``: leaves read back from a checkpoint that differ from
  what was saved, plus saves that never committed; its limit is 0.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

TINY_GRAD = 1e-3   # share of the median leaf's gradient norm


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   keep: Optional[np.ndarray] = None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    scale = np.maximum(ref, np.median(ref[keep]))
    return float(np.max(np.abs(prog - ref)[keep] / scale[keep]))


def compare(*, prog_losses: Sequence[float],
            extra_step3_losses: Sequence[float], prog_grad: np.ndarray,
            prog_change: np.ndarray, ref) -> Dict[str, float]:
    ref_losses = np.asarray(ref.losses, np.float64)
    gaps = [abs(a - b) for a, b in zip(prog_losses, ref_losses)]
    gaps += [abs(x - ref_losses[2]) for x in extra_step3_losses]
    moving = ref.raw_grad_norms >= TINY_GRAD * np.median(ref.raw_grad_norms)
    return {"loss_gap": float(max(gaps)),
            "grad_gap": worst_leaf_gap(prog_grad, ref.grad_norms),
            "change_gap": worst_leaf_gap(prog_change, ref.change_norms,
                                         moving)}


def judge(numbers: Dict[str, float], limits: Dict[str, Optional[float]]
          ) -> Dict[str, Dict[str, Optional[float]]]:
    """Each number beside its limit (``None``: not set, which fails)."""
    return {k: {"value": v, "limit": limits.get(k)}
            for k, v in numbers.items()}
