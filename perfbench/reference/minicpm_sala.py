"""MiniCPM-SALA (openbmb/MiniCPM-SALA `config.json`, `model_type`
`minicpm_sala`), forward pass to logits: float32 `jax.numpy`, matmul
precision `highest`, every layer by its definition. Loss and gradients
come from autodiff of `loss`.

With h = RMSNorm(x) (eps `rms_norm_eps`, learned weight), L the PUBLISHED
depth (`published.num_hidden_layers`, kept in the scale under a cut), no
bias anywhere:

  x = scale_emb * E[token]
  each layer: x += (scale_depth / sqrt(L)) * Mixer(RMSNorm(x))
              x += (scale_depth / sqrt(L)) * (silu(h Wg) * (h Wu)) Wd
  logits = (RMSNorm(x) / (hidden_size / dim_model_base)) W_head^T

`minicpm4` mixer: `num_attention_heads` query heads over
`num_key_value_heads` K/V heads of `head_dim`, query head i reading K/V
head i // group; no rotary; causal softmax(q k^T / sqrt(d)) v under an
explicit (T, T) mask; out = (A * sigmoid(h Wgate)) Wo. Beyond
`sparse.dense_len` positions the mask is InfLLM-v2's (MiniCPM4 report,
arXiv:2506.07900): compressed keys = means of k over whole windows of
`kernel_size` at `kernel_stride`; a query's group scores the compressed
keys it sees whole by softmax, summed over the group's query heads; a
block of `block_size` tokens takes the largest score of the windows that
overlap it; the query keeps the first `init_blocks` blocks, the blocks its
last `window_size` positions touch, and the `topk` best other blocks that
start at or before it (ties to the earlier block).

`lightning-attn` mixer: `lightning_nh` heads of `lightning_head_dim`;
RMSNorm over each head of q and of k (learned, `qk_norm`); rotary over the
whole head, halves paired, base `rope_theta`; o = ((q k^T) * D) v /
sqrt(d) with D_ts = lambda_h^(t-s) for s <= t, lambda_h = exp(-2^(-8 (h +
1) / H)), as the masked (T, T) product a head; out = (RMSNorm(o over all
heads) * sigmoid(h Wgate)) Wo.

Reads TransformerLM's flat parameter dict (`embed`, `head`, `lnf_g`,
`layer{i}_{ln1_g, wq, wk, wv, wg, wo, ln2_g, w_gate, w_in, w_out}` and, in
lightning layers, `q_norm_g`, `k_norm_g`, `o_norm_g`; matrices (in, out))
and the configuration's file. A layer at a time, each its own program over
that layer's weights cast to float32, attention a head at a time, so that
8,192 tokens at the published widths fit beside the training state.

`drop` names the controls a tolerance is shown to refuse (never part of a
comparison that decides `correct`): "decay" (lambda = 1), "rope" (no
rotary), "precision" (the mixers' matrix products on operands rounded to
float8_e4m3, per-tensor scaled: the nearest precision below bfloat16).
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
    """x (B, T, H, D): pairs (x_i, x_(i + D/2)) turned by t * theta^(-2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def _selection_mask(q, k, sp):
    """(B, G, T, T) bool: the keys each query's group may read, by
    InfLLM-v2 selection. q (B, T, H, D), k (B, T, G, D)."""
    b, t, h, d = q.shape
    g = k.shape[2]
    size, stride, block = sp["kernel_size"], sp["kernel_stride"], \
        sp["block_size"]
    n_blocks = -(-t // block)
    starts = list(range(0, t - size + 1, stride))
    pos = jnp.arange(t)
    blk = jnp.arange(n_blocks)
    forced = (blk[None, :] < sp["init_blocks"]) | \
        (blk[None, :] * block + block - 1 >= pos[:, None] - sp["window_size"]
         + 1)
    reach = blk[None, :] * block <= pos[:, None]
    if starts:
        ck = jnp.stack([jnp.mean(k[:, s:s + size], axis=1) for s in starts],
                       axis=1)                              # (B, W, G, D)
        first = jnp.asarray(starts)
        visible = first[None, :] + size - 1 <= pos[:, None]     # (T, W)
        qg = q.reshape(b, t, g, h // g, d)
        s = jnp.einsum("btgrd,bwgd->bgrtw", qg, ck) / math.sqrt(d)
        s = jnp.where(visible, s, -jnp.inf)
        e = jnp.where(visible, jnp.exp(s - jnp.max(
            jnp.where(visible, s, -1e30), -1, keepdims=True)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
        p = jnp.sum(p, axis=2)                              # (B, G, T, W)
        overlap = (first[:, None] < (blk[None, :] + 1) * block) & \
            (first[:, None] + size > blk[None, :] * block)      # (W, nb)
        score = jnp.max(jnp.where(overlap[None, None, None], p[..., None],
                                  0.0), axis=-2)            # (B, G, T, nb)
    else:
        score = jnp.zeros((b, g, t, n_blocks), F32)
    others = reach & ~forced
    order = jnp.argsort(jnp.where(others, -score, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    kept = (others & (rank < sp["topk"])) | (forced & reach)
    return jnp.repeat(kept, block, axis=-1)[..., :t] & \
        (pos[None, :] <= pos[:, None])


def _minicpm4(w, h, cfg, mm):
    b, t, _ = h.shape
    hd, heads, kv = cfg["head_dim"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    q = mm(h, w["wq"]).reshape(b, t, heads, hd)
    k = mm(h, w["wk"]).reshape(b, t, kv, hd)
    v = mm(h, w["wv"]).reshape(b, t, kv, hd)
    group = heads // kv
    pos = jnp.arange(t)
    if t > cfg["sparse"]["dense_len"]:
        mask = _selection_mask(q, k, cfg["sparse"])          # (B, G, T, T)
    else:
        mask = jnp.broadcast_to(pos[None, :] <= pos[:, None],
                                (b, kv, t, t))

    def head(i):
        qi = lax.dynamic_index_in_dim(q, i, 2, keepdims=False)  # (B, T, D)
        ki, vi = (lax.dynamic_index_in_dim(x, i // group, 2, keepdims=False)
                  for x in (k, v))
        mi = lax.dynamic_index_in_dim(mask, i // group, 1, keepdims=False)
        s = mm(qi, ki.swapaxes(1, 2)) / math.sqrt(hd)
        return mm(jax.nn.softmax(jnp.where(mi, s, -jnp.inf), -1), vi)
    a = lax.map(head, jnp.arange(heads))                     # (H, B, T, D)
    a = a.transpose(1, 2, 0, 3).reshape(b, t, heads * hd)
    return mm(a * jax.nn.sigmoid(mm(h, w["wg"])), w["wo"])


def _lightning(w, h, cfg, mm, drop):
    b, t, _ = h.shape
    hd, heads = cfg["lightning_head_dim"], cfg["lightning_nh"]
    q, k, v = (mm(h, w[n]).reshape(b, t, heads, hd)
               for n in ("wq", "wk", "wv"))
    if cfg["qk_norm"]:
        q = _rms(q, w["q_norm_g"], cfg["rms_norm_eps"])
        k = _rms(k, w["k_norm_g"], cfg["rms_norm_eps"])
    if cfg["lightning_use_rope"] and "rope" not in drop:
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    pos = jnp.arange(t, dtype=F32)
    gap = pos[:, None] - pos[None, :]
    rate = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=F32) / heads)
    if "decay" in drop:
        rate = jnp.zeros_like(rate)

    def head(i):
        qi, ki, vi = (lax.dynamic_index_in_dim(x, i, 2, keepdims=False)
                      for x in (q, k, v))
        decay = jnp.where(gap >= 0, jnp.exp(-rate[i] * jnp.maximum(gap, 0)),
                          0.0)
        return mm(mm(qi, ki.swapaxes(1, 2)) * decay, vi) / math.sqrt(hd)
    o = lax.map(head, jnp.arange(heads)).transpose(1, 2, 0, 3)
    o = _rms(o.reshape(b, t, heads * hd), w["o_norm_g"], cfg["rms_norm_eps"])
    return mm(o * jax.nn.sigmoid(mm(h, w["wg"])), w["wo"])


def _layer(w, x, kind, cfg, drop):
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        low = "precision" in drop
        mm = (lambda a, b: jnp.matmul(_fp8(a), _fp8(b))) if low \
            else jnp.matmul
        eps = cfg["rms_norm_eps"]
        depth = cfg["scale_depth"] / math.sqrt(
            cfg["published"]["num_hidden_layers"])
        h = _rms(x, w["ln1_g"], eps)
        mixed = _lightning(w, h, cfg, mm, drop) if kind == "lightning-attn" \
            else _minicpm4(w, h, cfg, mm)
        x = x + depth * mixed
        h = _rms(x, w["ln2_g"], eps)
        return x + depth * ((jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_in"]))
                            @ w["w_out"])


def _head(x, lnf_g, head, cfg):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, lnf_g.astype(F32), cfg["rms_norm_eps"])
        x = x / (cfg["hidden_size"] / cfg["dim_model_base"])
        return x @ head.astype(F32).T


def forward(params, tokens, cfg, drop=()):
    """tokens (B, T) int32 -> logits (B, T, vocabulary slice), float32. Not
    to be wrapped in a jit of its own: each kind of layer is one, over one
    layer's weights at a time, and they go when this call returns (a loaded
    program holds its temporaries on the device)."""
    kinds = {kind: jax.jit(functools.partial(_layer, kind=kind, cfg=cfg,
                                             drop=drop))
             for kind in set(cfg["mixer_types"])}
    x = cfg["scale_emb"] * params["embed"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        mine = {k[len(f"layer{i}_"):]: v for k, v in params.items()
                if k.startswith(f"layer{i}_")}
        x = kinds[cfg["mixer_types"][i]](mine, x)
    return jax.jit(functools.partial(_head, cfg=cfg))(
        x, params["lnf_g"], params["head"])


def loss(params, tokens, targets, cfg):
    """Mean next-token negative log-likelihood over the slice, float32."""
    logp = jax.nn.log_softmax(forward(params, tokens, cfg), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
