"""Operations and bytes computed from shapes, for MFU and roofline shares.

Model FLOPs count each multiply-add as two operations and a training step
as three forward passes (forward, and backward's two products). Recomputed
work under rematerialisation does not count.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from chipbench import spec

XOR_BLOCK_WORDS = 65_536   # the fused XOR kernel's tile: inputs pad to it


def forward_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """The whole layer stack's (from the architecture file) and the
    head's."""
    return spec.arch(cfg).forward_flops(cfg, seq_len) \
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    return 3 * forward_flops_per_token(cfg, seq_len)


def xor_checksum_bytes(chunk_nbytes: int) -> int:
    """HBM bytes of one fused XOR+digest call on a chunk: the current and
    previous words read, the delta written, each padded to the tile."""
    words = math.ceil(chunk_nbytes / 4)
    padded = math.ceil(words / XOR_BLOCK_WORDS) * XOR_BLOCK_WORDS
    return 3 * 4 * padded
