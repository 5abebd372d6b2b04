"""Laguna-S-2.1 (poolside/Laguna-S-2.1 `config.json`, `model_type`
`laguna`), forward pass to logits: float32 `jax.numpy`, matmul precision
`highest`, every layer by its definition, no kernel. Loss and gradients come
from autodiff of `loss`.

Pre-norm blocks, RMSNorm (eps `rms_norm_eps`, learned weight), no bias:

  x = E[token]
  each layer l: h = x + Attn_l(RMSNorm(x));  x = h + MLP_l(RMSNorm(h))
  logits = RMSNorm(x) W_head^T

Attn_l: H_l = `num_attention_heads_per_layer[l]` query heads over
`num_key_value_heads` K/V heads of `head_dim` D, query head i reading K/V
head i // (H_l / kv). Rotary by `rope_parameters[layer_types[l]]` on q and
k: over the first r = `partial_rotary_factor` D lanes of a head, lane i
paired with lane i + r/2; `default`: inv_freq_i = theta^(-2i/r); `yarn`:
inv_freq_i = (1 - ramp_i) theta^(-2i/r) + ramp_i theta^(-2i/r) / factor,
ramp_i = clip((i - low) / (high - low), 0, 1), low = floor(c(beta_fast)),
high = ceil(c(beta_slow)), c(n) = r ln(L / (2 pi n)) / (2 ln theta), L =
`original_max_position_embeddings`; cos and sin times `attention_factor`.
Scores q k^T / sqrt(D) under an explicit (T, T) mask: j <= i, and on
`sliding_attention` layers i - j < `sliding_window`. o_h = sigmoid(x Wg)_h
(P v)_h (`gating` per head, Wg hidden -> H_l), then Wo.

MLP_l: `mlp_layer_types[l]` "dense": (silu(h Wgate) * (h Wup)) Wdown at
`intermediate_size`. "sparse": s = score(h Wr) over all `published`
`num_experts` in float32 (sigmoid: `assumed.score`); the
`num_experts_per_tok` largest; w = `moe_routed_scaling_factor` s_sel /
sum(s_sel) (`norm_topk_prob`); y = sum over chosen AND HELD e of w_e
SwiGLU_e(h) + SwiGLU_shared(h), held = the `experts_held` range, each held
expert over every token and weighted by w_e (0 where it was not chosen).

Reads TransformerLM's flat parameter dict (`embed`, `head`, `lnf_g`,
`layer{i}_{ln1_g, wq, wk, wv, wg, wo, ln2_g}`, a dense layer's `w_gate`,
`w_in`, `w_out`, an expert layer's `router`, `e_gate_in` (held, d, 2 f: gate
then up), `e_out`, `s_gate`, `s_in`, `s_out`; matrices (in, out)) and the
configuration's file. A layer at a time, each its own program over that
layer's weights cast to float32, attention a head at a time, experts one at
a time, so that 8,192 tokens at the published widths fit on the chip.

`drop` names the controls a tolerance is shown to refuse (never part of a
comparison that decides `correct`): "window" (sliding layers read every
earlier key), "gate" (no output gate), "experts" (the routed part left
out), "yarn" (plain rotary at theta over the same lanes, no attention
factor), "precision" (every layer's matrix products on operands rounded to
float8_e4m3, per-tensor scaled: the nearest precision below bfloat16).
`choices` {layer: (N, k) expert indices} hands a layer the program's own
choices in place of the reference's top-k (its weights stay the reference's
scores at those experts): a second reading, which says how much of a
difference is routing and how much arithmetic.
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


def inv_freq(rope, head_dim, drop=()):
    """(frequencies of the r / 2 rotated pairs, r, the factor on cos and
    sin) of one `rope_parameters` entry."""
    r = int(head_dim * rope["partial_rotary_factor"])
    theta = float(rope["rope_theta"])
    i = jnp.arange(r // 2, dtype=F32)
    plain = theta ** (-2.0 * i / r)
    if rope["rope_type"] != "yarn" or "yarn" in drop:
        return plain, r, 1.0

    def lane(turns):
        return r * math.log(rope["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(lane(rope["beta_fast"])), 0)
    high = min(math.ceil(lane(rope["beta_slow"])), r - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1 - ramp) * plain + ramp * plain / rope["factor"], r, \
        float(rope["attention_factor"])


def _rotary(x, rope, drop):
    """x (T, H, D): the first r lanes of each head turned by t * inv_freq."""
    freq, r, factor = inv_freq(rope, x.shape[-1], drop)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq[None, :]
    cos, sin = (factor * f(angle)[:, None, :] for f in (jnp.cos, jnp.sin))
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _attention(w, h, kind, heads, cfg, mm, drop):
    t = h.shape[0]
    hd, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    rope = cfg["rope_parameters"][kind]
    q = _rotary(mm(h, w["wq"]).reshape(t, heads, hd), rope, drop)
    k = _rotary(mm(h, w["wk"]).reshape(t, kv, hd), rope, drop)
    v = mm(h, w["wv"]).reshape(t, kv, hd)
    pos = jnp.arange(t)
    mask = pos[None, :] <= pos[:, None]
    if kind == "sliding_attention" and "window" not in drop:
        mask &= pos[:, None] - pos[None, :] < cfg["sliding_window"]
    group = heads // kv

    def head(i):
        qi = lax.dynamic_index_in_dim(q, i, 1, keepdims=False)      # (T, D)
        ki, vi = (lax.dynamic_index_in_dim(x, i // group, 1, keepdims=False)
                  for x in (k, v))
        s = mm(qi, ki.T) / math.sqrt(hd)
        return mm(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), vi)
    a = lax.map(head, jnp.arange(heads)).transpose(1, 0, 2)     # (T, H, D)
    if "gate" not in drop:
        a = a * jax.nn.sigmoid(mm(h, w["wg"]))[..., None]
    return mm(a.reshape(t, heads * hd), w["wo"])


def _swiglu(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def route(h, router, cfg, chosen=None):
    """(experts (T, k), weights (T, k)) of the router on h (T, d), float32;
    `chosen` takes the top-k's place."""
    s = jax.nn.sigmoid(jnp.matmul(h, router, precision="highest"))
    if chosen is None:
        _, chosen = lax.top_k(s, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return chosen, cfg["moe_routed_scaling_factor"] * top


def _experts(w, h, cfg, mm, drop, chosen):
    f = cfg["moe_intermediate_size"]
    first, count = cfg["experts_held"]["first"], cfg["experts_held"]["count"]
    experts, weights = route(h, w["router"], cfg, chosen)
    y = _swiglu(h, w["s_gate"], w["s_in"], w["s_out"], mm)
    if "experts" in drop:
        return y, experts

    def one(y, e):
        gate_in = lax.dynamic_index_in_dim(w["e_gate_in"], e, 0, False)
        out = lax.dynamic_index_in_dim(w["e_out"], e, 0, False)
        mine = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        return y + mine[:, None] * _swiglu(h, gate_in[:, :f], gate_in[:, f:],
                                           out, mm), None
    return lax.scan(one, y, jnp.arange(count))[0], experts


def _layer(w, x, chosen, kind, heads, mlp, cfg, drop):
    """One block on ONE sequence x (T, d). Returns (x, the router's choices
    or None)."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        mm = (lambda a, b: jnp.matmul(_fp8(a), _fp8(b))) \
            if "precision" in drop else jnp.matmul
        eps = cfg["rms_norm_eps"]
        x = x + _attention(w, _rms(x, w["ln1_g"], eps), kind, heads, cfg, mm,
                           drop)
        h = _rms(x, w["ln2_g"], eps)
        if mlp == "dense":
            return x + _swiglu(h, w["w_gate"], w["w_in"], w["w_out"], mm), \
                None
        y, experts = _experts(w, h, cfg, mm, drop, chosen)
        return x + y, experts


def _head(x, lnf_g, head, cfg):
    with jax.default_matmul_precision("highest"):
        return _rms(x, lnf_g.astype(F32), cfg["rms_norm_eps"]) \
            @ head.astype(F32).T


def forward(params, tokens, cfg, drop=(), choices=None, with_choices=False):
    """tokens (B, T) int32 -> logits (B, T, vocabulary slice), float32; with
    `with_choices`, (logits, {layer: (B, T, k) the experts each token
    chose}). Not to be wrapped in a jit of its own: each kind of layer is
    one program, over one layer's weights and one sequence at a time."""
    depth = cfg["num_hidden_layers"]
    kinds = list(zip(cfg["layer_types"][:depth],
                     cfg["num_attention_heads_per_layer"][:depth],
                     cfg["mlp_layer_types"][:depth]))
    programs = {k: jax.jit(functools.partial(
        _layer, kind=k[0], heads=k[1], mlp=k[2], cfg=cfg, drop=tuple(drop)))
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
        jnp.stack(rows), params["lnf_g"], params["head"])
    if with_choices:
        return logits, {i: jnp.stack(v) for i, v in made.items()}
    return logits


def loss(params, tokens, targets, cfg):
    """Mean next-token negative log-likelihood over the slice, float32."""
    logp = jax.nn.log_softmax(forward(params, tokens, cfg), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
