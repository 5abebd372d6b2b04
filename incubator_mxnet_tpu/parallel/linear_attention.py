"""Decayed linear attention (Lightning Attention) in chunks, plain XLA.

    o_t = sum over s <= t of lambda_h^(t-s) (q_t . k_s) v_s

per head h, lambda_h = exp(-slope_h). Read as a recurrence it carries a
(D, E) state a head, S_t = lambda S_(t-1) + k_t v_t^T, o_t = q_t S_t: O(T)
work and no (T, T) array. `lightning_attention` computes it a chunk of C
tokens at a time: inside a chunk the masked product ((Q K^T) * M) V with
M_ij = lambda^(i-j) for i >= j (scope `lightning_intra`); between chunks
the float32 state S_c = lambda^C S_(c-1) + sum_j lambda^(C-1-j) k_j v_j^T,
read by the chunk's queries through lambda^(i+1) q_i S_c (scope
`lightning_state`). Every chunk's intra part and every chunk's own
contribution to the state are batched matrix products over all chunks at
once; only the (D, E) states are carried, by a scan over T / C steps of one
multiply-add each. Every decay factor is at most 1: nothing overflows, and
a head whose lambda^C underflows simply forgets.

The backward pass is written out (custom_vjp): it keeps q, k and v, and
recomputes the chunk scores and both states, the forward one for dq and
its mirror image, R_c = lambda^C R_(c+1) + sum_i lambda^i q_i dO_i^T over
the chunks AFTER c, for dk and dv. Nothing of size (T, T) or (T, C) is
saved between the passes.

Matrix products take their operands in the input dtype with float32
accumulation (bfloat16 at the MXU's full rate; float32 inputs at HIGHEST
precision, as flash_attention._prec has it), the states as they are,
float32; decays multiply float32 accumulators wherever the decayed index
is not the contracted one.

`linear_attention_reference` is the recurrence itself, token by token in
float32: the oracle of tests/test_linear_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["lightning_attention", "linear_attention_reference",
           "alibi_slopes"]


def alibi_slopes(n_heads):
    """Lightning Attention's per-head decay rates, ALiBi's slopes:
    slope_h = 2^(-8 (h + 1) / H), lambda_h = exp(-slope_h)."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / n_heads)


def linear_attention_reference(q, k, v, slopes):
    """The recurrence, one token a step, float32. q, k (B, T, H, D), v
    (B, T, H, E), slopes (H,) -> (B, T, H, E) float32."""
    f32 = jnp.float32
    q, k, v = (x.astype(f32) for x in (q, k, v))
    lam = jnp.exp(-slopes.astype(f32))[None, :, None, None]

    def step(state, qkv):
        qt, kt, vt = qkv                                # (B, H, D | E)
        state = lam * state + kt[..., :, None] * vt[..., None, :]
        return state, jnp.sum(qt[..., :, None] * state, axis=-2)
    B, _, H, D = q.shape
    _, out = lax.scan(step, jnp.zeros((B, H, D, v.shape[-1]), f32),
                      tuple(x.swapaxes(0, 1) for x in (q, k, v)))
    return out.swapaxes(0, 1)


def _tables(slopes, c):
    """Decay factors of a chunk of c tokens, float32, laid out to multiply
    (B, N, c, H, .) arrays and (B, N, H, c, c) scores: lambda^i and
    lambda^(i+1) by query row i, lambda^(c-1-j) and lambda^(c-j) by key row
    j (each (c, H, 1)), the causal M (H, c, c), and lambda^c (H, 1, 1)."""
    s = slopes.astype(jnp.float32)
    i = jnp.arange(c, dtype=jnp.float32)
    rows = lambda power: jnp.exp(-power[:, None] * s[None, :])[..., None]
    diff = i[:, None] - i[None, :]
    m = jnp.where(diff >= 0,
                  jnp.exp(-s[:, None, None] * jnp.maximum(diff, 0.0)), 0.0)
    return dict(q0=rows(i), q1=rows(i + 1), k1=rows(c - 1 - i),
                k0=rows(c - i), m=m, chunk=jnp.exp(-s * c)[:, None, None])


def _states(own, decay, reverse=False):
    """The state each chunk reads, from every chunk's own contribution
    `own` (B, N, H, D, E) float32: S_0 = 0, S_(c+1) = decay * S_c + own_c;
    with `reverse`, from the chunks after it instead."""
    def step(state, mine):
        return decay * state + mine, state
    _, seen = lax.scan(step, jnp.zeros_like(own[:, 0]),
                       own.swapaxes(0, 1), reverse=reverse)
    return seen.swapaxes(0, 1)


def _ein(spec, a, b):
    """float32 a . b at the precision a's dtype asks for (_prec). Against a
    float32 state, b, a bfloat16 a is widened and the product is the MXU's
    one bfloat16 pass all the same; XLA's CPU backend has no bfloat16
    product of that shape."""
    from .flash_attention import _prec
    return jnp.einsum(spec, a.astype(b.dtype), b, precision=_prec(a.dtype),
                      preferred_element_type=jnp.float32)


def _decayed(x, table):
    """x times a decay table, rounded back to x's dtype: an operand whose
    decayed index is the contracted one."""
    return (x.astype(jnp.float32) * table).astype(x.dtype)


def _forward(q, k, v, slopes, scale):
    """q, k (B, N, C, H, D), v (B, N, C, H, E) -> o (B, N, C, H, E)."""
    t = _tables(slopes, q.shape[2])
    with jax.named_scope("lightning_intra"):
        scores = _ein("bnihd,bnjhd->bnhij", q, k) * t["m"]
        out = _ein("bnhij,bnjhe->bnihe", scores.astype(v.dtype), v)
    with jax.named_scope("lightning_state"):
        own = _ein("bnjhd,bnjhe->bnhde", _decayed(k, t["k1"]), v)
        seen = _states(own, t["chunk"])
        out = out + t["q1"] * _ein("bnihd,bnhde->bnihe", q, seen)
    return (scale * out).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked(q, k, v, slopes, scale):
    return _forward(q, k, v, slopes, scale)


def _chunked_fwd(q, k, v, slopes, scale):
    return _forward(q, k, v, slopes, scale), (q, k, v, slopes)


def _chunked_bwd(scale, res, do):
    q, k, v, slopes = res
    t = _tables(slopes, q.shape[2])
    with jax.named_scope("lightning_intra"):
        scores = (_ein("bnihd,bnjhd->bnhij", q, k) * t["m"]).astype(q.dtype)
        dscores = (_ein("bnihe,bnjhe->bnhij", do, v) * t["m"]).astype(q.dtype)
        dq = _ein("bnhij,bnjhd->bnihd", dscores, k)
        dk = _ein("bnhij,bnihd->bnjhd", dscores, q)
        dv = _ein("bnhij,bnihe->bnjhe", scores, do)
    with jax.named_scope("lightning_state"):
        own = _ein("bnjhd,bnjhe->bnhde", _decayed(k, t["k1"]), v)
        seen = _states(own, t["chunk"])
        dq = dq + t["q1"] * _ein("bnihe,bnhde->bnihd", do, seen)
        later = _ein("bnihd,bnihe->bnhde", _decayed(q, t["q0"]), do)
        after = _states(later, t["chunk"], reverse=True)
        dk = dk + t["k0"] * _ein("bnjhe,bnhde->bnjhd", v, after)
        dv = dv + t["k0"] * _ein("bnjhd,bnhde->bnjhe", k, after)
    return ((scale * dq).astype(q.dtype), (scale * dk).astype(k.dtype),
            (scale * dv).astype(v.dtype), jnp.zeros_like(slopes))


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def lightning_attention(q, k, v, slopes, chunk=256, scale=1.0):
    """Causal decayed linear attention in chunks of `chunk` tokens, times
    `scale` (applied to the float32 results, not to a rounded q). q, k
    (B, T, H, D), v (B, T, H, E), slopes (H,) float32 decay rates (a
    constant: no gradient flows to it). Returns (B, T, H, E) in q's dtype.
    A length that is no multiple of the chunk is padded with zero keys and
    values after the last token, which no earlier token sees."""
    B, T, H, _ = q.shape
    c = min(chunk, T)
    pad = -T % c
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    split = lambda x: x.reshape(B, (T + pad) // c, c, H, x.shape[-1])
    out = _chunked(split(q), split(k), split(v),
                   lax.stop_gradient(slopes.astype(jnp.float32)), scale)
    return out.reshape(B, T + pad, H, v.shape[-1])[:, :T]
