"""Architecture ``dense``: pre-norm decoder layers of grouped-query
attention with rotary embeddings over a sliding window and a GELU MLP,
with biases and LayerNorm (StarCoder2).

Each architecture file gives the layer's parameters in the program's
layout (``block_spec``), its float32 reference (``block``), the widths the
program's ``ModelConfig`` has to show (``program_widths``) and the layer's
forward FLOPs per token (``layer_forward_flops``).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import BF16, F32, Leaf, einsum, layer_norm, ln_spec


def norm_epsilon(cfg):
    return cfg["norm_epsilon"]


def program_widths(cfg):
    return dict(n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], window=cfg["sliding_window"],
                rope_theta=cfg["rope_theta"], use_bias=cfg["use_bias"])


def block_spec(cfg):
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    s = 1.0 / math.sqrt(d)
    out_scale = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])
    return {
        "ln1": ln_spec(d), "ln2": ln_spec(d),
        "attn": {"wq": Leaf((d, H * hd), BF16, "normal", s),
                 "wk": Leaf((d, KV * hd), BF16, "normal", s),
                 "wv": Leaf((d, KV * hd), BF16, "normal", s),
                 "wo": Leaf((H * hd, d), BF16, "normal", s * out_scale),
                 "bq": Leaf((H * hd,), BF16, "zeros"),
                 "bk": Leaf((KV * hd,), BF16, "zeros"),
                 "bv": Leaf((KV * hd,), BF16, "zeros"),
                 "bo": Leaf((d,), BF16, "zeros")},
        "ffn": {"w_up": Leaf((d, f), BF16, "normal", s),
                "w_down": Leaf((f, d), BF16, "normal",
                               out_scale / math.sqrt(f)),
                "b_up": Leaf((f,), BF16, "zeros"),
                "b_down": Leaf((d,), BF16, "zeros")},
    }


def avg_keys(seq_len, window):
    """Mean number of keys a causal query attends to over one sequence."""
    w = min(window or seq_len, seq_len)
    full = w * (w + 1) / 2 + (seq_len - w) * w
    return full / seq_len


def layer_forward_flops(cfg, seq_len):
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    macs = d * H * hd + 2 * d * KV * hd + H * hd * d + 2 * d * f
    attn = 2 * H * hd * avg_keys(seq_len, cfg.get("sliding_window", 0))
    return 2 * macs + 2 * attn


def rope(x, theta):
    """Rotary embedding, halves convention. x: (B, S, H, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(cfg, p, x, lowp):
    B, S, d = x.shape
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = norm_epsilon(cfg)
    h = layer_norm(x, p["ln1"], eps)
    a = p["attn"]
    q = (einsum("bsd,de->bse", h, a["wq"], lowp) + a["bq"]).reshape(
        B, S, H, hd)
    k = (einsum("bsd,de->bse", h, a["wk"], lowp) + a["bk"]).reshape(
        B, S, KV, hd)
    v = (einsum("bsd,de->bse", h, a["wv"], lowp) + a["bv"]).reshape(
        B, S, KV, hd)
    q = rope(q, cfg["rope_theta"])
    k = rope(k, cfg["rope_theta"])
    i = np.arange(S)[:, None]
    j = np.arange(S)[None, :]
    allowed = jnp.asarray((j <= i) & (j > i - cfg["sliding_window"]))
    rep = H // KV

    @jax.checkpoint
    def one_head(hidx):
        qh = jax.lax.dynamic_index_in_dim(q, hidx, 2, keepdims=False)
        kh = jax.lax.dynamic_index_in_dim(k, hidx // rep, 2, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, hidx // rep, 2, keepdims=False)
        logits = einsum("bsd,btd->bst", qh, kh, lowp) / math.sqrt(hd)
        logits = jnp.where(allowed, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return einsum("bst,btd->bsd", probs, vh, lowp)

    heads = jax.lax.map(one_head, jnp.arange(H))          # (H, B, S, hd)
    o = heads.transpose(1, 2, 0, 3).reshape(B, S, H * hd)
    x = x + einsum("bse,ed->bsd", o, a["wo"], lowp) + a["bo"]
    h2 = layer_norm(x, p["ln2"], eps)
    m = p["ffn"]
    up = einsum("bsd,df->bsf", h2, m["w_up"], lowp) + m["b_up"]
    act = jax.nn.gelu(up, approximate=True)
    return x + einsum("bsf,fd->bsd", act, m["w_down"], lowp) + m["b_down"]
