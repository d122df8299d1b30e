"""Flash attention kernel (Pallas TPU) — the model zoo's compute hot-spot.

TPU-native adaptation of the FlashAttention blocking scheme: the grid is
(batch·heads, q-blocks, kv-blocks); the last grid dimension is sequential on
TPU, so the online-softmax state (row max m, row sum l, accumulator acc)
lives in VMEM scratch across kv steps. Block shapes are MXU-aligned
(q_block × head_dim and kv_block × head_dim tiles, lane dim = head_dim,
sublane = block rows; defaults 256×128 fp32 = 128 KiB per operand tile).

Supports causal "full", sliding-"window" and "chunked" (block-local) masks —
the three attention variants in the assigned architectures. GQA is handled
by the wrapper (`ops.flash_attention`) which folds the group dim into heads.

Validated in interpret mode against ``ref.flash_attention_ref`` (and against
``repro.models.layers.blocked_sdpa``, the pure-XLA production path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Q_BLOCK = 256
KV_BLOCK = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  kind: str, window: int, chunk: int, scale: float,
                  kv_block: int, q_block: int, n_kv: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale            # (qb, hd)
    k = k_ref[0].astype(jnp.float32)                    # (kvb, hd)
    v = v_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (qb, kvb)

    qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                   logits.shape, 0)
    kpos = kj * kv_block + jax.lax.broadcasted_iota(jnp.int32,
                                                    logits.shape, 1)
    mask = kpos <= qpos
    if kind == "window":
        mask &= kpos > qpos - window
    elif kind == "chunked":
        mask &= (qpos // chunk) == (kpos // chunk)
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    p = jnp.where(mask, p, 0.0)
    l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kj == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / (l_scr[...] + 1e-30)).astype(o_ref.dtype)


def flash_attention_bh(q, k, v, *, interpret: bool, kind: str = "full",
                       window: int = 0, chunk: int = 0,
                       q_block: int = Q_BLOCK,
                       kv_block: int = KV_BLOCK) -> jax.Array:
    """q: (BH, S, hd); k/v: (BH, T, hd) — batch and heads pre-folded."""
    BH, S, hd = q.shape
    T = k.shape[1]
    qb = min(q_block, S)
    kvb = min(kv_block, T)
    assert S % qb == 0 and T % kvb == 0
    n_q = S // qb
    n_kv = T // kvb
    grid = (BH, n_q, n_kv)
    kernel = functools.partial(
        _flash_kernel, kind=kind, window=window, chunk=chunk,
        scale=1.0 / (hd ** 0.5), kv_block=kvb, q_block=qb, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, qb, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kvb, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kvb, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
