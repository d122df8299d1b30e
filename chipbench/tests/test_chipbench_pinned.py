"""What the architecture files must not move: the weights drawn from a
seed, the reference's loss and gradient on a fixed batch, and the model
FLOPs of the chip configurations. The expected values in
``data/pinned.json`` were read from the tree before the architecture
files gave the whole stack; ``python3 -m chipbench.tests.test_chipbench_pinned``
prints them anew."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

from chipbench import flops, harness, reference, spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**31 + 5
TINY = ("tiny-dense", "tiny-rwkv")
CHIP = ("starcoder2-7b-l1v8", "rwkv6-7b-l1v8")


def digests(tree):
    """Each leaf's shape, dtype and bytes, hashed."""
    out = {}
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        x = np.asarray(x)
        h = hashlib.sha256(f"{x.shape} {x.dtype} ".encode())
        h.update(x.tobytes())
        out[jax.tree_util.keystr(path)] = h.hexdigest()[:16]
    return out


def tiny_readings(name):
    """Each leaf's digest of ``make_state``, and the reference's loss and
    each leaf's gradient norm on the first batch of a 2x32 feed."""
    cfg = spec.load_json(os.path.join(DATA, name + ".json"))
    params, opt = reference.make_state(cfg, SEED)
    tokens = harness.TokenFeed(SEED, 2, 32, cfg["vocab_size"]).tokens(0)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: reference.reference_loss(cfg, p, t)))(
                opt["master"], tokens)
    return {"digests": digests((params, opt)), "loss": float(loss),
            "grad_norms": [float(g) for g in reference.leaf_norms(grads)]}


def chip_flops(name):
    cfg = spec.load_json(os.path.join(spec.HERE, "configs", name + ".json"))
    return flops.forward_flops_per_token(cfg, 4096)


def pinned():
    return spec.load_json(os.path.join(DATA, "pinned.json"))


@pytest.mark.parametrize("name", TINY)
def test_weights_loss_and_gradient_are_pinned(name):
    want = pinned()[name]
    got = tiny_readings(name)
    assert got["digests"] == want["digests"]
    assert got["loss"] == pytest.approx(want["loss"], rel=1e-6)
    assert got["grad_norms"] == pytest.approx(want["grad_norms"], rel=1e-6)


@pytest.mark.parametrize("name", CHIP)
def test_forward_flops_are_pinned(name):
    assert chip_flops(name) == pinned()[name]["forward_flops_per_token"]


if __name__ == "__main__":
    out = {n: tiny_readings(n) for n in TINY}
    out.update({n: {"forward_flops_per_token": chip_flops(n)} for n in CHIP})
    print(json.dumps(out, indent=1))
