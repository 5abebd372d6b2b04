"""GPT-2 (Radford et al. 2019), forward pass to logits.

Pre-norm blocks, learned absolute positions, causal softmax attention
scaled by 1/sqrt(head size), tanh-approximated GELU, LayerNorm eps 1e-5,
output projection tied to the token embedding. Departure from the
published model, because the program's TransformerLM has none: no biases
on the linear layers. Reads TransformerLM's flat parameter dict
(`embed`, `pos_embed`, `layer{i}_{ln1_g,ln1_b,wq,wk,wv,wo,ln2_g,ln2_b,
w_in,w_out}`, `lnf_g`, `lnf_b`; matrices stored (in, out)).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _ln(x, g, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(
        math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params, tokens, cfg):
    """tokens (B, T) int32 -> logits (B, T, vocab), float32 throughout."""
    with jax.default_matmul_precision("highest"):
        p = {k: v.astype(jnp.float32) for k, v in params.items()}
        b, t = tokens.shape
        heads = cfg["n_heads"]
        hd = cfg["d_model"] // heads
        x = p["embed"][tokens] + p["pos_embed"][:t]
        causal = jnp.tril(jnp.ones((t, t), bool))
        for i in range(cfg["n_layers"]):
            w = lambda s: p[f"layer{i}_{s}"]            # noqa: E731
            h = _ln(x, w("ln1_g"), w("ln1_b"))
            q, k, v = ((h @ w(s)).reshape(b, t, heads, hd)
                       for s in ("wq", "wk", "wv"))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
            scores = jnp.where(causal, scores, -jnp.inf)
            attn = jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, -1), v)
            x = x + attn.reshape(b, t, -1) @ w("wo")
            h = _ln(x, w("ln2_g"), w("ln2_b"))
            x = x + _gelu_tanh(h @ w("w_in")) @ w("w_out")
        x = _ln(x, p["lnf_g"], p["lnf_b"])
        return x @ p["embed"].T
