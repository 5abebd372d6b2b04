"""Ring attention: exact attention over sequences sharded across devices.

The reference has NO sequence/context parallelism (SURVEY.md §5.7 — its
longest-sequence story is BucketingModule + fused RNN). This module is the
TPU-native capability that replaces it at pod scale: the sequence axis lives
on a mesh axis ("sp"); K/V blocks rotate around the ring with
`lax.ppermute` while each device accumulates its queries' attention in
log-sum-exp form, so peak memory is O(seq/devices) and the N^2 score
matrix never materializes globally.

Since round 4 each hop's local attention runs the Pallas flash-attention
FORWARD kernel (parallel/flash_attention.py) when the local shard tiles —
the kernel emits exactly the (out, lse) pair the ring merge needs, so the
per-hop score matrix does not materialize even locally. Untileable
shards keep the dense einsum hop. The hop loop is unrolled over the
(static) ring size; XLA overlaps each hop's ppermute with the next
block's compute either way.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "ring_attention_sharded", "attention_reference"]


def attention_reference(q, k, v, causal=False, sm_scale=None, window=None):
    """Plain single-device attention, the numeric oracle for the ring version.
    `window`: with `causal`, query i reads keys j with i - j < window.
    q,k,v: (B, T, H, D). f32 inputs run HIGHEST-precision einsums so the
    fallback matches the Pallas kernels' dtype-dependent precision (on
    TPU, DEFAULT would demote f32 operands to bf16)."""
    from .flash_attention import _prec
    B, T, H, D = q.shape
    prec = _prec(q.dtype)
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(D).astype(q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, k.shape[1]), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((T, k.shape[1]), bool), -window)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=prec)


def _dense_hop(q, k, v, scale, mask):
    """One (q_shard, k_shard) attention in (normalized out, lse) form.
    Returns out (B,t,H,D) f32 and lse (B,H,t) f32 (-inf on fully-masked
    rows)."""
    from .flash_attention import _prec
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=(prec := _prec(q.dtype)),
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v,
                   precision=prec, preferred_element_type=jnp.float32)
    denom = jnp.where(l > 0, l, 1.0)
    out = o / jnp.transpose(denom, (0, 2, 1))[..., None]
    lse = jnp.where(l > 0, m_safe + jnp.log(denom), -jnp.inf)
    return out, lse


def _flash_hop(q, k, v, scale, causal):
    """One hop through the Pallas flash forward kernel; differentiable
    in (out, lse) — flash_attention.flash_hop carries the custom vjp
    that runs the flash backward kernels with the lse cotangent folded
    into delta."""
    from .flash_attention import flash_hop

    return flash_hop(q, k, v, causal, scale)


def _flash_ok(q):
    from .flash_attention import _blocks_for

    B, t, H, D = q.shape
    return _blocks_for(t, t, D) is not None


def _merge(o_acc, lse_acc, o_b, lse_b):
    """log-sum-exp merge of two normalized partial attentions."""
    lse_new = jnp.logaddexp(lse_acc, lse_b)
    safe = jnp.where(jnp.isfinite(lse_new), lse_new, 0.0)
    c_old = jnp.where(jnp.isfinite(lse_acc), jnp.exp(lse_acc - safe), 0.0)
    c_new = jnp.where(jnp.isfinite(lse_b), jnp.exp(lse_b - safe), 0.0)
    to_bqhd = lambda c: jnp.transpose(c, (0, 2, 1))[..., None]
    return o_acc * to_bqhd(c_old) + o_b * to_bqhd(c_new), lse_new


def ring_attention(q, k, v, axis_name, causal=False, sm_scale=None):
    """Runs INSIDE shard_map: q,k,v are the local sequence shards (B,t,H,D);
    axis_name is the sp mesh axis. Exact (non-approximate) attention."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, t, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    use_flash = _flash_ok(q)
    if use_flash:
        try:
            # the flash kernel bakes the scale into the compiled program;
            # a traced scale (learned temperature) keeps the dense path,
            # which accepts it like the pre-flash implementation did
            scale = float(scale)
        except jax.errors.ConcretizationTypeError:
            use_flash = False

    o_acc = jnp.zeros((B, t, H, D), jnp.float32)
    lse_acc = jnp.full((B, H, t), -jnp.inf, jnp.float32)
    k_cur, v_cur = k, v
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    # hop i holds the K/V shard of device (my_idx - i) % axis_size. The
    # loop is unrolled (axis_size is static): hop 0 is the diagonal —
    # the only causally-masked block — so the flash kernel's causal mode
    # applies exactly there and every other hop is an unmasked kernel
    # call gated by src < mine.
    for i in range(axis_size):
        src_idx = (my_idx - i) % axis_size
        if use_flash:
            o_b, lse_b = _flash_hop(q, k_cur, v_cur, scale,
                                    causal and i == 0)
        else:
            if causal and i == 0:
                mask = jnp.tril(jnp.ones((t, t), bool))[None, None]
            else:
                mask = None
            o_b, lse_b = _dense_hop(q, k_cur, v_cur, scale, mask)
        if causal and i > 0:
            # whole-shard validity: strictly-earlier shards attend fully,
            # later shards not at all (same compute every device — the
            # SPMD ring steps in lockstep; a masked hop just merges -inf)
            lse_b = jnp.where(src_idx < my_idx, lse_b, -jnp.inf)
        o_acc, lse_acc = _merge(o_acc, lse_acc, o_b, lse_b)
        if i + 1 < axis_size:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)

    return o_acc.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False,
                           sm_scale=None):
    """shard_map wrapper: q,k,v (B,T,H,D) get sharded on T over `axis_name`
    (and batch over 'dp' if present) and attention runs as a ring."""
    from jax.sharding import PartitionSpec as P
    from ._compat import shard_map

    batch_axis = "dp" if "dp" in mesh.axis_names else None
    spec = P(batch_axis, axis_name, None, None)

    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal,
                          sm_scale=sm_scale),
        mesh, (spec, spec, spec), spec)
    return fn(q, k, v)
