"""Architecture ``gated_moe``: a layer stack of several block kinds, for the
CPU tests. Leading dense layers of full attention and a gated SiLU FFN,
then a repeated pattern of MoE layers (the ``_moe`` kinds), each with the
window or full attention its kind names; RMSNorm throughout, no biases
(see ``chipbench/arch/dense.py`` for what an architecture file gives).

The MoE reference is plain top-k routing over softmax router
probabilities, the chosen gates renormalised, with the Switch
load-balancing loss per routing group. It holds where the program's
capacity covers the whole group (``capacity_factor`` at least experts /
top-k), so that no token is dropped.
"""

import math

import jax
import jax.numpy as jnp

from chipbench import spec
from chipbench.reference import BF16, F32, Leaf, einsum, rms_norm, rms_spec

dense = spec.load_module(spec.ARCH_DIR, "dense")


def layer_groups(cfg):
    pattern = tuple(cfg["moe_pattern"])
    first = cfg["first_dense_layers"]
    reps, rest = divmod(cfg["num_hidden_layers"] - first, len(pattern))
    if rest:
        raise ValueError("the MoE layers are not whole repetitions of "
                         "the pattern")
    return ((("full",), first), (pattern, reps))


def final_norm_spec(cfg):
    return rms_spec(cfg["hidden_size"])


def final_norm(cfg, p, x):
    return rms_norm(x, p, cfg["rms_norm_eps"])


def aux_weight(cfg):
    return cfg["router_aux_loss_coef"]


def program_widths(cfg):
    return dict(n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                window=cfg["sliding_window"], rope_theta=cfg["rope_theta"],
                use_bias=False, norm="rmsnorm", act="silu",
                n_experts=cfg["num_local_experts"],
                top_k=cfg["num_experts_per_tok"],
                capacity_factor=cfg["capacity_factor"],
                moe_group_size=cfg["router_group_size"],
                shared_expert=False,
                router_aux_coef=cfg["router_aux_loss_coef"])


def is_moe(kind):
    return kind.endswith("_moe")


def window_of(cfg, kind):
    return cfg["sliding_window"] if kind.startswith("window") else 0


def block_spec(cfg, kind):
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    E = cfg["num_local_experts"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    s = 1.0 / math.sqrt(d)
    out_scale = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])
    p = {"ln1": rms_spec(d), "ln2": rms_spec(d),
         "attn": {"wq": Leaf((d, H * hd), BF16, "normal", s),
                  "wk": Leaf((d, KV * hd), BF16, "normal", s),
                  "wv": Leaf((d, KV * hd), BF16, "normal", s),
                  "wo": Leaf((H * hd, d), BF16, "normal", s * out_scale)}}
    if is_moe(kind):
        p["moe"] = {"router": Leaf((d, E), F32, "normal", s),
                    "w_gate": Leaf((E, d, f), BF16, "normal", s),
                    "w_up": Leaf((E, d, f), BF16, "normal", s),
                    "w_down": Leaf((E, f, d), BF16, "normal",
                                   out_scale / math.sqrt(f))}
    else:
        p["ffn"] = {"w_gate": Leaf((d, f), BF16, "normal", s),
                    "w_up": Leaf((d, f), BF16, "normal", s),
                    "w_down": Leaf((f, d), BF16, "normal",
                                   out_scale / math.sqrt(f))}
    return p


def block_flops(cfg, kind, seq_len):
    """Forward FLOPs per token of one layer: attention, and the FFN, or the
    router and the experts a token is routed to."""
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    ffn = 2 * 3 * d * f
    if is_moe(kind):
        ffn = 2 * d * cfg["num_local_experts"] \
            + cfg["num_experts_per_tok"] * ffn
    return dense.attention_flops(cfg, seq_len, window_of(cfg, kind)) + ffn


def forward_flops(cfg, seq_len):
    return sum(count * block_flops(cfg, kind, seq_len)
               for pattern, count in layer_groups(cfg) for kind in pattern)


def gated_ffn(w_gate, w_up, w_down, h, lowp):
    g = einsum("bsd,df->bsf", h, w_gate, lowp)
    u = einsum("bsd,df->bsf", h, w_up, lowp)
    return einsum("bsf,fd->bsd", jax.nn.silu(g) * u, w_down, lowp)


def moe(cfg, m, h, lowp):
    """Top-k mixture of gated SiLU experts. Returns (output, aux): aux is
    the Switch loss, experts^2 / top-k times the sum over experts of the
    share of routings and the mean router probability, averaged over the
    routing groups of ``router_group_size`` tokens of each row."""
    B, S, d = h.shape
    E, K = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    gs = cfg["router_group_size"]
    if S % gs:
        raise ValueError("a routing group has to lie within one row")
    if cfg["capacity_factor"] * K < E:
        raise ValueError("the capacity has to cover the group: plain top-k "
                         "routing drops no token")
    probs = jax.nn.softmax(einsum("bsd,de->bse", h, m["router"], lowp), -1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-9)
    chosen = jax.nn.one_hot(idx, E, dtype=F32)                # (B,S,K,E)
    weight = jnp.einsum("bske,bsk->bse", chosen, gate)

    @jax.checkpoint
    def expert(e):
        return gated_ffn(m["w_gate"][e], m["w_up"][e], m["w_down"][e], h,
                         lowp) * weight[..., e, None]

    out = jax.lax.map(expert, jnp.arange(E)).sum(0)
    share = chosen.sum(2).reshape(B, S // gs, gs, E).mean(2)
    mean_prob = probs.reshape(B, S // gs, gs, E).mean(2)
    aux = (share * mean_prob).sum(-1).mean() * E ** 2 / K
    return out, aux


def block(cfg, kind, p, x, lowp):
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["ln1"], eps)
    x = x + dense.attention(cfg, p["attn"], h, window_of(cfg, kind), lowp)
    h2 = rms_norm(x, p["ln2"], eps)
    if is_moe(kind):
        y, aux = moe(cfg, p["moe"], h2, lowp)
    else:
        f = p["ffn"]
        y = gated_ffn(f["w_gate"], f["w_up"], f["w_down"], h2, lowp)
        aux = jnp.zeros((), F32)
    return x + y, aux
