"""Architecture ``dense``: pre-norm decoder layers of grouped-query
attention with rotary embeddings over a sliding window and a GELU MLP,
with biases and LayerNorm (StarCoder2).

Each architecture file gives everything the benchmark knows of one
architecture, so that a configuration is added by files alone:

* ``layer_groups(cfg)``: the stack as ``((pattern, count), ...)`` in the
  program's block names, the program's ``ModelConfig.layer_groups``;
* ``block_spec(cfg, kind)``: one layer's parameters of that block kind, in
  the program's layout, with ``ln1`` and ``ln2``;
* ``block(cfg, kind, p, x, lowp) -> (x, aux)``: its float32 reference,
  ``aux`` a float32 scalar (zero for a kind with no auxiliary loss);
* ``final_norm_spec(cfg)`` and ``final_norm(cfg, p, x)``: the norm before
  the head;
* ``aux_weight(cfg)``: the weight of the summed ``aux`` in the loss;
* ``program_widths(cfg)``: every field of the program's ``ModelConfig``,
  past ``d_model``, ``vocab`` and ``n_layers``, that has to match;
* ``forward_flops(cfg, seq_len)``: the whole stack's forward FLOPs per
  token, the head left out.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import BF16, F32, Leaf, einsum, layer_norm, ln_spec


KIND = "window"


def layer_groups(cfg):
    return (((KIND,), cfg["num_hidden_layers"]),)


def norm_epsilon(cfg):
    return cfg["norm_epsilon"]


def final_norm_spec(cfg):
    return ln_spec(cfg["hidden_size"])


def final_norm(cfg, p, x):
    return layer_norm(x, p, norm_epsilon(cfg))


def aux_weight(cfg):
    return 0.0


def program_widths(cfg):
    return dict(n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                window=cfg["sliding_window"],
                rope_theta=cfg["rope_theta"], use_bias=cfg["use_bias"],
                norm="layernorm", act="gelu_mlp")


def block_spec(cfg, kind):
    assert kind == KIND, kind
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


def attention_flops(cfg, seq_len, window):
    """Projections and the causal QK^T and PV over ``window`` keys (0:
    all), per token."""
    d = cfg["hidden_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    macs = d * H * hd + 2 * d * KV * hd + H * hd * d
    return 2 * macs + 2 * 2 * H * hd * avg_keys(seq_len, window)


def forward_flops(cfg, seq_len):
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    layer = attention_flops(cfg, seq_len, cfg["sliding_window"]) \
        + 2 * 2 * d * f
    return cfg["num_hidden_layers"] * layer


def rope(x, theta):
    """Rotary embedding, halves convention. x: (B, S, H, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _project(h, a, w, b, lowp):
    y = einsum("bsd,de->bse", h, a[w], lowp)
    return y + a[b] if b in a else y


def attention(cfg, a, h, window, lowp):
    """Causal grouped-query self-attention with rotary embeddings over
    ``window`` keys (0: all), one head at a time, with the input
    projections' biases where ``a`` has them. Returns the output
    projection; its bias ``bo``, if any, is the caller's to add."""
    B, S, d = h.shape
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = _project(h, a, "wq", "bq", lowp).reshape(B, S, H, hd)
    k = _project(h, a, "wk", "bk", lowp).reshape(B, S, KV, hd)
    v = _project(h, a, "wv", "bv", lowp).reshape(B, S, KV, hd)
    q = rope(q, cfg["rope_theta"])
    k = rope(k, cfg["rope_theta"])
    i = np.arange(S)[:, None]
    j = np.arange(S)[None, :]
    allowed = (j <= i) & (j > i - window) if window else j <= i
    allowed = jnp.asarray(allowed)
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
    return einsum("bse,ed->bsd", o, a["wo"], lowp)


def block(cfg, kind, p, x, lowp):
    assert kind == KIND, kind
    eps = norm_epsilon(cfg)
    h = layer_norm(x, p["ln1"], eps)
    a = p["attn"]
    x = x + attention(cfg, a, h, cfg["sliding_window"], lowp) + a["bo"]
    h2 = layer_norm(x, p["ln2"], eps)
    m = p["ffn"]
    up = einsum("bsd,df->bsf", h2, m["w_up"], lowp) + m["b_up"]
    act = jax.nn.gelu(up, approximate=True)
    x = x + einsum("bsf,fd->bsd", act, m["w_down"], lowp) + m["b_down"]
    return x, jnp.zeros((), F32)
