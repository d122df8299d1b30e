"""The benchmark's own weights and its plain reference of each architecture.

Nothing here imports the program, and nothing here is particular to one
architecture: the layer stack, each block kind's parameters and reference,
the final norm and the weight of any auxiliary loss come from
``chipbench/arch/<arch>.py``, found from the configuration's ``arch``.
``param_spec`` lays out a configuration's parameters in the tree the
program's ``Trainer`` holds (the benchmark checks the two layouts agree
before it installs its weights); ``make_state`` draws them on the device
from the seed in one jitted call. The reference is straightforward float32
``jax.numpy`` at ``highest`` matmul precision. ``train_reference``
follows the first training steps with AdamW and returns the numbers that the
check compares: each step's loss, each leaf's norm of the first (clipped)
gradient, and each leaf's norm of the parameters' change after the steps.

``lowp=True`` rounds every matmul input to float8 (e4m3): the control, one
precision below the bfloat16 that the configurations state.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec

F32 = jnp.float32
BF16 = jnp.bfloat16


# ------------------------------------------------------------------ weights
class Leaf(NamedTuple):
    shape: tuple
    dtype: Any
    init: str            # normal | uniform | zeros | ones | w0
    scale: float = 1.0


def ln_spec(d):
    return {"scale": Leaf((d,), F32, "ones"), "bias": Leaf((d,), F32, "zeros")}


def rms_spec(d):
    return {"scale": Leaf((d,), F32, "ones")}


def _stack(tree, n):
    return jax.tree_util.tree_map(
        lambda l: Leaf((n,) + l.shape, l.dtype, l.init, l.scale), tree,
        is_leaf=lambda x: isinstance(x, Leaf))


def param_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Every parameter's shape, dtype and init, in the program's layout:
    ``groups`` is a tuple over the architecture's layer groups, each a
    tuple over its pattern's positions, each position's block stacked over
    the group's count; the blocks and ``ln_f`` from the architecture
    file."""
    V = cfg["vocab_size"]
    d = cfg["hidden_size"]
    arch = spec.arch(cfg)
    groups = tuple(tuple(_stack(arch.block_spec(cfg, kind), count)
                         for kind in pattern)
                   for pattern, count in arch.layer_groups(cfg))
    return {"embed": {"embed": Leaf((V, d), BF16, "normal", 0.02),
                      "head": Leaf((d, V), BF16, "normal", 0.02)},
            "ln_f": arch.final_norm_spec(cfg),
            "groups": groups}


def _is_leaf(x):
    return isinstance(x, Leaf)


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole seed, 32 bits or more."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _draw(leaf: Leaf, key):
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, leaf.dtype)
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, leaf.dtype)
    if leaf.init == "uniform":
        x = jax.random.uniform(key, leaf.shape, F32) * leaf.scale
    elif leaf.init == "w0":   # RWKV decay base: exp(-exp(w0)) per channel
        x = jax.random.normal(key, leaf.shape, F32) * 0.5 - 0.6
    else:
        x = jax.random.normal(key, leaf.shape, F32) * leaf.scale
    return x.astype(leaf.dtype)


def init_params(spec, key):
    leaves, tdef = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)
    return tdef.unflatten([_draw(l, jax.random.fold_in(key, i))
                           for i, l in enumerate(leaves)])


def _static(cfg) -> str:
    """The configuration's numbers, names and lists (a layer pattern) as a
    hashable static argument of a jitted call."""
    return json.dumps({k: v for k, v in cfg.items()
                       if isinstance(v, (int, float, str, list))},
                      sort_keys=True)


@functools.partial(jax.jit, static_argnums=(0,))
def _make_state(cfg_json, key):
    params = init_params(param_spec(json.loads(cfg_json)), key)
    f32 = jax.tree_util.tree_map(lambda x: x.astype(F32), params)
    zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.zeros(x.shape, F32), params)
    return params, {"master": f32, "m": zeros(), "v": zeros(),
                    "count": jnp.zeros((), jnp.int32)}


@functools.partial(jax.jit, static_argnums=(0,))
def _make_params(cfg_json, key):
    return init_params(param_spec(json.loads(cfg_json)), key)


def make_state(cfg, seed: int):
    """(bf16 params, AdamW state with fp32 master, m, v) on the device."""
    return _make_state(_static(cfg), seed_key(seed))


def make_params(cfg, seed: int):
    """The bf16 params of ``make_state`` alone."""
    return _make_params(_static(cfg), seed_key(seed))


# --------------------------------------------------------------- reference
def _round(a, lowp):
    a = a.astype(F32)
    return a.astype(jnp.float8_e4m3fn).astype(F32) if lowp else a


def einsum(eq, a, b, lowp):
    return jnp.einsum(eq, _round(a, lowp), _round(b, lowp),
                      precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rms_norm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def reference_loss(cfg, params, tokens, lowp: bool = False):
    """Mean next-token cross-entropy over a block of rows (B, S), plus the
    architecture's weight times the blocks' summed auxiliary losses. The
    blocks run in the program's scan order: groups, then repetitions, then
    pattern positions."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    d = cfg["hidden_size"]
    x = p["embed"]["embed"][tokens] * math.sqrt(d)
    arch = spec.arch(cfg)
    aux = jnp.zeros((), F32)
    for (pattern, count), group in zip(arch.layer_groups(cfg), p["groups"]):
        for rep in range(count):
            for kind, stacked in zip(pattern, group):
                lp = jax.tree_util.tree_map(lambda a: a[rep], stacked)
                x, a = jax.checkpoint(
                    lambda xx, pp, kind=kind: arch.block(cfg, kind, pp, xx,
                                                         lowp))(x, lp)
                aux = aux + a
    x = arch.final_norm(cfg, p["ln_f"], x)
    logits = einsum("bsd,dv->bsv", x[:, :-1], p["embed"]["head"], lowp)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return (logz - gold).mean() + arch.aux_weight(cfg) * aux


# ---------------------------------------------------------------- training
@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def diff_norms(a, b):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)
                                                  - y.astype(F32))))
                      for x, y in zip(jax.tree_util.tree_leaves(a),
                                      jax.tree_util.tree_leaves(b))])


class Readings(NamedTuple):
    losses: np.ndarray      # (steps,)
    grad_norms: np.ndarray  # (leaves,) first step, clipped, as AdamW gets it
    raw_grad_norms: np.ndarray  # (leaves,) first step, before clipping
    change_norms: np.ndarray    # (leaves,) |master_after - master_0|


def _adamw(hp, master, m, v, grads, count):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                      for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, hp["grad_clip"] / (gn + 1e-9))
    b1c = 1.0 - hp["b1"] ** count
    b2c = 1.0 - hp["b2"] ** count

    def upd(g, m_, v_, w):
        g = g * scale
        m_ = hp["b1"] * m_ + (1 - hp["b1"]) * g
        v_ = hp["b2"] * v_ + (1 - hp["b2"]) * g * g
        w = w - hp["lr"] * ((m_ / b1c) / (jnp.sqrt(v_ / b2c) + hp["eps"])
                            + hp["weight_decay"] * w)
        return g, m_, v_, w

    leaves_g, tdef = jax.tree_util.tree_flatten(grads)
    out = [upd(*a) for a in zip(leaves_g, tdef.flatten_up_to(m),
                                tdef.flatten_up_to(v),
                                tdef.flatten_up_to(master))]
    return tuple(tdef.unflatten([o[i] for o in out]) for i in range(4))


def train_reference(cfg, params0, batches: Sequence[np.ndarray], *,
                    lowp: bool = False, rows_used: int = 0) -> Readings:
    """Follow ``len(batches)`` AdamW steps from ``params0`` in float32.

    Each batch's gradient is accumulated over its rows one at a time, so
    the reference fits beside nothing else on one chip. ``rows_used`` > 0
    averages the loss over the first rows only (the half-batch fault)."""
    hp = {k: float(v) for k, v in cfg["optimizer"].items()}
    with jax.default_matmul_precision("highest"):
        grad_row = jax.jit(jax.value_and_grad(
            lambda p, t: reference_loss(cfg, p, t, lowp)))
        add = jax.jit(lambda a, b, w: jax.tree_util.tree_map(
            lambda x, y: x + w * y, a, b))
        adamw = jax.jit(functools.partial(_adamw, hp))
        master0 = jax.tree_util.tree_map(lambda x: x.astype(F32), params0)
        master = master0
        m = jax.tree_util.tree_map(jnp.zeros_like, master0)
        v = jax.tree_util.tree_map(jnp.zeros_like, master0)
        losses, first = [], None
        for step, tokens in enumerate(batches, start=1):
            rows = rows_used or tokens.shape[0]
            grads, loss = None, 0.0
            for r in range(rows):
                lr_, gr = grad_row(master, jnp.asarray(tokens[r:r + 1]))
                grads = gr if grads is None else add(grads, gr, 1.0)
                loss += float(lr_)
                del gr
            grads = jax.tree_util.tree_map(lambda g: g / rows, grads)
            losses.append(loss / rows)
            clipped, m, v, master = adamw(master, m, v, grads,
                                          jnp.float32(step))
            if first is None:
                first = (np.asarray(leaf_norms(clipped)),
                         np.asarray(leaf_norms(grads)))
            del grads, clipped
        change = np.asarray(diff_norms(master, master0))
    return Readings(np.asarray(losses), first[0], first[1], change)
