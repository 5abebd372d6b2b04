"""LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B `config.json`, `model_type`
`lfm2_moe`), forward pass to logits: float32 `jax.numpy`, matmul precision
`highest`, every layer by its definition, no kernel. Loss and gradients come
from autodiff of `loss`.

Pre-norm blocks, RMSNorm (eps `norm_eps`, learned weight), no bias:

  x = E[token]
  each layer l: h = x + Mixer_l(RMSNorm(x));  x = h + MLP_l(RMSNorm(h))
  logits = RMSNorm(x) E^T                        (the head tied to E)

Mixer_l by `layer_types[l]`. "conv", LFM2's gated short convolution:
B, C, x~ = split(h W_bcx) (hidden -> 3 hidden, in that order), u = B * x~,
v_t = sum over j < L of w_j * u_(t - L + 1 + j) with u = 0 before t = 0 (L =
`conv_L_cache`, written out as a loop over the taps), y = (C * v) W_o.
"full_attention": H = `num_attention_heads` query heads over
`num_key_value_heads` K/V heads of D = hidden / H, query head i reading K/V
head i // (H / kv); an RMSNorm of D with a learned weight on each head of q
and of k; rotary over the whole head at `rope_theta`, lane i paired with lane
i + D/2; scores q k^T / sqrt(D) under an explicit (T, T) causal mask; no
output gate; then W_o.

MLP_l: the first `num_dense_layers` layers a SwiGLU (silu(h Wgate) *
(h Wup)) Wdown at `intermediate_size`. The others: s = sigmoid(h Wr) over
all `published` `num_experts` in float32; the `num_experts_per_tok` largest
of s + b chosen (b the layer's expert bias); w = `routed_scaling_factor` s /
sum(s) over the chosen (`norm_topk_prob`); y = sum over chosen AND HELD e of
w_e SwiGLU_e(h) at `moe_intermediate_size`, held = the `experts_held` range,
each held expert over every token and weighted by w_e (0 where it was not
chosen). No shared expert.

Reads TransformerLM's flat parameter dict (`embed`, `lnf_g`,
`layer{i}_{ln1_g, ln2_g}`, a conv layer's `w_bcx`, `conv_w` (L, d), `wo`, an
attention layer's `wq`, `wk`, `wv`, `wo`, `q_norm_g`, `k_norm_g`, a dense
layer's `w_gate`, `w_in`, `w_out`, an expert layer's `router`, `e_bias`,
`e_gate_in` (held, d, 2 f: gate then up), `e_out`; matrices (in, out)) and
the configuration's file. A layer at a time, each its own program over that
layer's weights cast to float32, attention a head at a time, experts one at
a time, so that 8,192 tokens at the published widths fit on the chip.

`drop` names the controls a limit is shown to refuse (never part of a
comparison that decides `correct`): "taps" (the convolution left out: v =
u), "reversed" (the taps read forward, anti-causal: v_t = sum w_j *
u_(t + L - 1 - j)), "qk_norm" (q and k unnormed), "bias" (the choice by s
alone), "precision" (every layer's matrix products on operands rounded to
float8_e4m3, per-tensor scaled: the nearest precision below bfloat16).
`choices` {layer: (T, k) expert indices, a sequence's} hands a layer the
program's own choices in place of the reference's top-k (its weights stay the
reference's scores at those experts).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def _rotary(x, theta):
    """x (T, H, D): the halves of each head turned by t * theta^(-2i/D)."""
    d = x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=F32) / d)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def conv_taps(u, w, drop=()):
    """The depthwise convolution of u (T, C) over the taps w (L, C), one
    shifted product a tap: causal, or read forward under "reversed"."""
    t, taps = u.shape[0], w.shape[0]
    if "taps" in drop:
        return u
    rest = [(0, 0)] * (u.ndim - 1)
    v = jnp.zeros_like(u)
    for j in range(taps):
        shift = taps - 1 - j            # tap j reads position t - shift
        if "reversed" in drop:
            moved = jnp.pad(u[shift:], [(0, shift)] + rest)
        else:
            moved = jnp.pad(u[:t - shift], [(shift, 0)] + rest)
        v = v + w[j] * moved
    return v


def _conv(w, h, mm, drop):
    b, c, xt = jnp.split(mm(h, w["w_bcx"]), 3, axis=-1)
    return mm(c * conv_taps(b * xt, w["conv_w"], drop), w["wo"])


def _attention(w, h, cfg, mm, drop):
    t = h.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // heads
    q = mm(h, w["wq"]).reshape(t, heads, hd)
    k = mm(h, w["wk"]).reshape(t, kv, hd)
    v = mm(h, w["wv"]).reshape(t, kv, hd)
    if "qk_norm" not in drop:
        q = _rms(q, w["q_norm_g"], cfg["norm_eps"])
        k = _rms(k, w["k_norm_g"], cfg["norm_eps"])
    q, k = (_rotary(x, float(cfg["rope_theta"])) for x in (q, k))
    pos = jnp.arange(t)
    mask = pos[None, :] <= pos[:, None]
    group = heads // kv

    def head(i):
        qi = lax.dynamic_index_in_dim(q, i, 1, keepdims=False)      # (T, D)
        ki, vi = (lax.dynamic_index_in_dim(x, i // group, 1, keepdims=False)
                  for x in (k, v))
        s = mm(qi, ki.T) / math.sqrt(hd)
        return mm(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), vi)
    a = lax.map(head, jnp.arange(heads)).transpose(1, 0, 2)     # (T, H, D)
    return mm(a.reshape(t, heads * hd), w["wo"])


def _swiglu(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def route(h, router, bias, cfg, chosen=None, drop=()):
    """(experts (T, k), weights (T, k)) of the router on h (T, d), float32:
    the k largest of s + bias chosen (of s alone under "bias"), weighted by
    s; `chosen` takes the top-k's place."""
    s = jax.nn.sigmoid(jnp.matmul(h, router, precision="highest"))
    if chosen is None:
        by = s if "bias" in drop else s + bias
        _, chosen = lax.top_k(by, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return chosen, cfg["routed_scaling_factor"] * top


def _experts(w, h, cfg, mm, drop, chosen):
    f = cfg["moe_intermediate_size"]
    first, count = cfg["experts_held"]["first"], cfg["experts_held"]["count"]
    experts, weights = route(h, w["router"], w["e_bias"], cfg, chosen, drop)

    def one(y, e):
        gate_in = lax.dynamic_index_in_dim(w["e_gate_in"], e, 0, False)
        out = lax.dynamic_index_in_dim(w["e_out"], e, 0, False)
        mine = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        return y + mine[:, None] * _swiglu(h, gate_in[:, :f], gate_in[:, f:],
                                           out, mm), None
    return lax.scan(one, jnp.zeros_like(h), jnp.arange(count))[0], experts


def _layer(w, x, chosen, kind, dense, cfg, drop):
    """One block on ONE sequence x (T, d). Returns (x, the router's choices
    or None)."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        mm = (lambda a, b: jnp.matmul(_fp8(a), _fp8(b))) \
            if "precision" in drop else jnp.matmul
        eps = cfg["norm_eps"]
        h = _rms(x, w["ln1_g"], eps)
        x = x + (_conv(w, h, mm, drop) if kind == "conv"
                 else _attention(w, h, cfg, mm, drop))
        h = _rms(x, w["ln2_g"], eps)
        if dense:
            return x + _swiglu(h, w["w_gate"], w["w_in"], w["w_out"], mm), \
                None
        y, experts = _experts(w, h, cfg, mm, drop, chosen)
        return x + y, experts


def _head(x, lnf_g, embed, cfg):
    with jax.default_matmul_precision("highest"):
        return _rms(x, lnf_g.astype(F32), cfg["norm_eps"]) \
            @ embed.astype(F32).T


def layers(cfg):
    """(mixer kind, dense MLP or not) of each layer as run."""
    depth = cfg["num_hidden_layers"]
    return [(kind, i < cfg["num_dense_layers"])
            for i, kind in enumerate(cfg["layer_types"][:depth])]


def forward(params, tokens, cfg, drop=(), choices=None, with_choices=False):
    """tokens (B, T) int32 -> logits (B, T, vocabulary slice), float32; with
    `with_choices`, (logits, {layer: (B, T, k) the experts each token
    chose}). `choices` {layer: (B, T, k)}. Not to be wrapped in a jit of its
    own: each kind of layer is one program, over one layer's weights and one
    sequence at a time."""
    kinds = layers(cfg)
    programs = {k: jax.jit(functools.partial(
        _layer, kind=k[0], dense=k[1], cfg=cfg, drop=tuple(drop)))
        for k in set(kinds)}
    rows, made = [], {}
    for b in range(tokens.shape[0]):
        x = params["embed"][tokens[b]].astype(F32)
        for i, kind in enumerate(kinds):
            mine = {k[len(f"layer{i}_"):]: v for k, v in params.items()
                    if k.startswith(f"layer{i}_")}
            chosen = None if choices is None or i not in choices \
                else choices[i][b]
            x, experts = programs[kind](mine, x, chosen)
            if experts is not None:
                made.setdefault(i, []).append(experts)
        rows.append(x)
    logits = jax.jit(functools.partial(_head, cfg=cfg))(
        jnp.stack(rows), params["lnf_g"], params["embed"])
    if with_choices:
        return logits, {i: jnp.stack(v) for i, v in made.items()}
    return logits


def loss(params, tokens, targets, cfg):
    """Mean next-token negative log-likelihood over the slice, float32."""
    logp = jax.nn.log_softmax(forward(params, tokens, cfg), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
