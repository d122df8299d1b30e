"""Architecture ``rwkv6``: RWKV-6 (Finch) layers, a time mix with
data-dependent decay and a channel mix, the recurrence written step by
step (see ``dense.py`` for what an architecture file gives)."""

import math

import jax
import jax.numpy as jnp

from chipbench.reference import BF16, F32, Leaf, einsum, layer_norm, ln_spec

MIX_LORA = 32      # the repository's time-mix LoRA width (Finch 7B: 32)
TIME_CHUNK = 64    # steps between checkpoints of the reference recurrence
KIND = "rwkv"


def layer_groups(cfg):
    return (((KIND,), cfg["num_hidden_layers"]),)


def norm_epsilon(cfg):
    return cfg["layer_norm_epsilon"]


def final_norm_spec(cfg):
    return ln_spec(cfg["hidden_size"])


def final_norm(cfg, p, x):
    return layer_norm(x, p, norm_epsilon(cfg))


def aux_weight(cfg):
    return 0.0


def program_widths(cfg):
    return dict(d_ff=cfg["intermediate_size"],
                rwkv_head_size=cfg["head_size"],
                rwkv_decay_lora=cfg["time_decay_extra_dim"],
                norm="layernorm")


def block_spec(cfg, kind):
    assert kind == KIND, kind
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    hs = cfg["head_size"]
    dl = cfg["time_decay_extra_dim"]
    ml = cfg["time_mix_extra_dim"]
    s = 1.0 / math.sqrt(d)
    out_scale = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])
    return {
        "ln1": ln_spec(d), "ln2": ln_spec(d),
        "tmix": {"mix_base": Leaf((5, d), F32, "uniform", 0.5),
                 "mix_w1": Leaf((d, 5 * ml), BF16, "normal", s),
                 "mix_w2": Leaf((5, ml, d), BF16, "normal", 0.01),
                 "w0": Leaf((d,), F32, "w0"),
                 "w_a": Leaf((d, dl), BF16, "normal", s),
                 "w_b": Leaf((dl, d), BF16, "normal", 0.01),
                 "u": Leaf((d // hs, hs), F32, "normal", 0.1),
                 "wr": Leaf((d, d), BF16, "normal", s),
                 "wk": Leaf((d, d), BF16, "normal", s),
                 "wv": Leaf((d, d), BF16, "normal", s),
                 "wg": Leaf((d, d), BF16, "normal", s),
                 "wo": Leaf((d, d), BF16, "normal", s * out_scale),
                 "ln_x_scale": Leaf((d,), F32, "ones")},
        "cmix": {"mix_k": Leaf((d,), F32, "uniform", 0.5),
                 "mix_r": Leaf((d,), F32, "uniform", 0.5),
                 "w_in": Leaf((d, f), BF16, "normal", s),
                 "w_out": Leaf((f, d), BF16, "normal",
                               out_scale / math.sqrt(f)),
                 "w_r": Leaf((d, d), BF16, "normal", s)},
    }


def forward_flops(cfg, seq_len):
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    hs = cfg["head_size"]
    ml, dl = cfg["time_mix_extra_dim"], cfg["time_decay_extra_dim"]
    macs = (2 * 5 * ml * d + 2 * dl * d + 5 * d * d     # time mix
            + 2 * d * f + d * d)                        # channel mix
    # recurrence: state update, bonus and readout per state element
    wkv = (d // hs) * 3 * hs * hs
    return cfg["num_hidden_layers"] * (2 * macs + 2 * wkv)


def wkv6_stepwise(r, k, v, lw, u):
    """RWKV-6 recurrence, one step at a time. r, k, v, lw: (B, T, H, hs);
    u: (H, hs). o_t = r_t (S + diag(u) k_t^T v_t); S <- diag(e^lw_t) S +
    k_t^T v_t. Checkpointed every TIME_CHUNK steps for the backward."""
    B, T, H, hs = r.shape
    C = TIME_CHUNK if T % TIME_CHUNK == 0 else 1
    xs = tuple(a.transpose(1, 0, 2, 3).reshape(T // C, C, B, H, hs)
               for a in (r, k, v, lw))

    def step(S, x):
        r_t, k_t, v_t, lw_t = x
        kv = k_t[..., :, None] * v_t[..., None, :]
        o = jnp.einsum("bhi,bhij->bhj", r_t, S + u[None, :, :, None] * kv,
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.exp(lw_t)[..., :, None] * S + kv, o

    @jax.checkpoint
    def chunk(S, x):
        return jax.lax.scan(step, S, x)

    S0 = jnp.zeros((B, H, hs, hs), F32)
    _, out = jax.lax.scan(chunk, S0, xs)
    return out.reshape(T, B, H, hs).transpose(1, 0, 2, 3)


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def block(cfg, kind, p, x, lowp):
    assert kind == KIND, kind
    B, T, d = x.shape
    hs = cfg["head_size"]
    H = d // hs
    eps = norm_epsilon(cfg)
    t = p["tmix"]
    h = layer_norm(x, p["ln1"], eps)
    delta = _shift(h) - h
    base = h + delta * t["mix_base"][0]
    lora = jnp.tanh(einsum("btd,de->bte", base, t["mix_w1"], lowp))
    lora = lora.reshape(B, T, 5, MIX_LORA)
    mixes = t["mix_base"][None, None] + einsum("btki,kid->btkd", lora,
                                               t["mix_w2"], lowp)
    xr, xw, xk, xv, xg = (h + delta * mixes[:, :, i] for i in range(5))
    r = einsum("btd,de->bte", xr, t["wr"], lowp).reshape(B, T, H, hs)
    k = einsum("btd,de->bte", xk, t["wk"], lowp).reshape(B, T, H, hs)
    v = einsum("btd,de->bte", xv, t["wv"], lowp).reshape(B, T, H, hs)
    g = jax.nn.silu(einsum("btd,de->bte", xg, t["wg"], lowp))
    dec = einsum("btl,ld->btd",
                 jnp.tanh(einsum("btd,dl->btl", xw, t["w_a"], lowp)),
                 t["w_b"], lowp)
    lw = -jnp.exp(t["w0"] + dec).reshape(B, T, H, hs)
    wkv = wkv6_stepwise(r, k, v, lw, t["u"]).reshape(B, T, H, hs)
    mu = wkv.mean(-1, keepdims=True)
    var = ((wkv - mu) ** 2).mean(-1, keepdims=True)
    gn = ((wkv - mu) / jnp.sqrt(var + cfg["group_norm_epsilon"])).reshape(
        B, T, d) * t["ln_x_scale"]
    x = x + einsum("btd,de->bte", gn * g, t["wo"], lowp)
    c = p["cmix"]
    h2 = layer_norm(x, p["ln2"], eps)
    d2 = _shift(h2) - h2
    kk = jnp.square(jax.nn.relu(einsum("btd,df->btf", h2 + d2 * c["mix_k"],
                                       c["w_in"], lowp)))
    rr = jax.nn.sigmoid(einsum("btd,de->bte", h2 + d2 * c["mix_r"],
                               c["w_r"], lowp))
    x = x + rr * einsum("btf,fd->btd", kk, c["w_out"], lowp)
    return x, jnp.zeros((), F32)
