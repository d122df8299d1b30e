"""Warm-up of the ``delta`` route: compile the fused XOR+digest kernel for
every chunk length a delta save of the routed leaves hands it, with one
call on zeros each, so that no save in the window compiles."""

import jax
import numpy as np


def warm(leaves, chunk_bytes):
    from repro.kernels import ops as kops

    lengths = set()
    for x in leaves:
        n = x.nbytes
        if n >= chunk_bytes:
            lengths.add(chunk_bytes)
        if n % chunk_bytes:
            lengths.add(n % chunk_bytes)
    for n in sorted(lengths):
        z = np.zeros(n, np.uint8)
        jax.block_until_ready(kops.fused_xor_checksum(z, z))
