"""Model FLOPs per token against a hand count, and kernel bytes."""

import math
import os

from chipbench import flops, spec


def config(name):
    return spec.load_json(os.path.join(spec.HERE, "configs", f"{name}.json"))


def test_starcoder2_flops_per_token_by_hand():
    cfg = config("starcoder2-7b-l1v8")
    d, f, V, S = 4608, 18432, 6144, 4096
    q_o = 2 * d * 36 * 128               # wq and wo
    kv = 2 * d * 4 * 128                 # wk and wv
    mlp = 2 * d * f
    keys = (S + 1) / 2                   # causal, the window covers S
    attn = 2 * 36 * 128 * keys           # QK^T and PV multiply-adds
    fwd = 2 * (q_o + kv + mlp) + 2 * attn + 2 * d * V
    assert math.isclose(flops.forward_flops_per_token(cfg, S), fwd)
    assert math.isclose(flops.train_flops_per_token(cfg, S), 3 * fwd)
    assert math.isclose(3 * fwd / 1e9, 1.58547456, rel_tol=1e-6)


def test_rwkv6_flops_per_token_by_hand():
    cfg = config("rwkv6-7b-l1v8")
    d, f, V, hs = 4096, 14336, 8192, 64
    tmix = 5 * d * d + 5 * 32 * d * 2 + 64 * d * 2   # r k v g o, LoRAs
    cmix = 2 * d * f + d * d
    wkv = (d // hs) * 3 * hs * hs
    fwd = 2 * (tmix + cmix) + 2 * wkv + 2 * d * V
    assert math.isclose(flops.forward_flops_per_token(cfg, 4096), fwd)


def test_moe_flops_count_the_routed_experts_by_hand():
    cfg = spec.load_json(os.path.join(spec.HERE, "tests", "data",
                                      "tiny-moe.json"))
    cfg["arch_dir"] = os.path.join(spec.HERE, "tests", "data", "arch")
    d, f, V, S, E, K, W = 64, 128, 256, 32, 4, 2, 8
    proj = 2 * (d * 4 * 16 + 2 * d * 2 * 16 + 4 * 16 * d)
    full = proj + 2 * 2 * 4 * 16 * (S + 1) / 2
    window = proj + 2 * 2 * 4 * 16 * (W * (W + 1) / 2 + (S - W) * W) / S
    ffn = 2 * 3 * d * f
    moe = 2 * d * E + K * ffn            # the router, and K experts of E
    fwd = (full + ffn) + 2 * (window + moe + full + moe) + 2 * d * V
    assert math.isclose(flops.forward_flops_per_token(cfg, S), fwd)


def test_window_shorter_than_sequence():
    # each of 8 queries sees min(i + 1, 4) keys: 1+2+3+4*5 = 26
    dense = spec.load_module(spec.ARCH_DIR, "dense")
    assert math.isclose(dense.avg_keys(8, 4), 26 / 8)


def test_xor_checksum_bytes_pad_to_the_tile():
    four_mib = 4 << 20
    assert flops.xor_checksum_bytes(four_mib) == 3 * four_mib
    # 18 KiB pads to one 256 KiB tile of words
    assert flops.xor_checksum_bytes(18432) == 3 * 4 * 65536
    assert flops.xor_checksum_bytes(1) == 3 * 4 * 65536
