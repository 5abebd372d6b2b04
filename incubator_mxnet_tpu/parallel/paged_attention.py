"""Ragged paged attention for autoregressive decode, as an XLA gather.

The serving-side sibling of flash_attention.py, following "Ragged Paged
Attention" (arXiv:2604.15464): at decode time every sequence in the
batch has a DIFFERENT context length, and its KV history lives in
fixed-size pages scattered across a shared pool rather than one
contiguous (B, T_max, H, D) buffer. Attention therefore reads through a
per-sequence page table.

Layouts::

    q          (B, H, D)        one query token per active sequence
    k_pages    (P, page_size, H, D)   the shared KV pool (keys)
    v_pages    (P, page_size, H, D)   the shared KV pool (values)
    page_table (B, max_pages)   int32 page ids, row-major per sequence
    seq_lens   (B,) int32       valid context length per sequence

Contract: positions ``t < seq_lens[b]`` of sequence ``b`` live at pool
row ``page_table[b, t // page_size] * page_size + t % page_size``.
``seq_lens`` values below 1 are CLAMPED to 1 (an idle batch slot still
attends to exactly one — arbitrary — key, so its output is finite;
callers ignore idle-slot outputs).

Both entry points are plain XLA compositions: gather each sequence's
pages into a dense (B, max_pages*page_size, H, D) view, then masked
softmax attention. That costs O(B * T_max) memory a step, the copy a
kernel walking (sequence, page) with scalar-prefetched page-table
entries would avoid. Such a Pallas kernel existed and lost its only
race on the chip, 152.7 ms a decode step to this composition's 9.7 ms
(PERF.md section 6, PR 21): a decode query is one row per head, too
thin for the MXU, so the kernel ran on the VPU. A kernel returns when a
``/generate`` cell (ROADMAP W2) names a shape, with a race on the device
clock.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

from .flash_attention import _prec

__all__ = ["paged_attention", "paged_attention_multiquery"]

_NEG_INF = -1e30


def _scale(sm_scale, d):
    return sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    sm_scale=None):
    """Ragged paged attention over a shared KV page pool (see the module
    docstring for layouts): one query token per sequence."""
    B, H, D = q.shape
    seq_lens = jnp.maximum(seq_lens, 1)
    k = k_pages[page_table].reshape(B, -1, H, D)     # (B, T, H, D)
    v = v_pages[page_table].reshape(B, -1, H, D)
    prec = _prec(q.dtype)
    qs = q * jnp.asarray(_scale(sm_scale, D), q.dtype)
    # s[b, h, t] = sum_d qs[b, h, d] * k[b, t, h, d]  (b, h batched)
    s = lax.dot_general(qs, k, (((2,), (3,)), ((0, 1), (0, 2))),
                        precision=prec,
                        preferred_element_type=jnp.float32)
    t_ids = lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(t_ids < seq_lens[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    # o[b, h, d] = sum_t p[b, h, t] * v[b, t, h, d]  (b, h batched)
    o = lax.dot_general(p, v, (((2,), (1,)), ((0, 1), (0, 2))),
                        precision=prec,
                        preferred_element_type=jnp.float32)
    return (o / l).astype(q.dtype)


def paged_attention_multiquery(q, k_pages, v_pages, page_table, seq_lens,
                               sm_scale=None):
    """The speculative-decode verify read path. Verify scores G = k+1
    positions of every sequence in ONE step, so each sequence contributes
    a BLOCK of G query tokens, each attending to a different-length prefix
    of the same pages::

        q          (B, G, H, D)     G stacked query tokens per sequence
        seq_lens   (B, G) int32     context length per (sequence, query)

    Everything else (pool layout, page-table indirection, clamp-to-1 on
    idle rows) is the single-query contract. The gather is shared: one
    dense view of a sequence's pages serves all G queries, so verify
    costs one pass over the KV history, not G."""
    B, G, H, D = q.shape
    seq_lens = jnp.maximum(seq_lens, 1)                  # (B, G)
    k = k_pages[page_table].reshape(B, -1, H, D)         # (B, T, H, D)
    v = v_pages[page_table].reshape(B, -1, H, D)
    prec = _prec(q.dtype)
    qs = q * jnp.asarray(_scale(sm_scale, D), q.dtype)
    # s[b, h, g, t] = sum_d qs[b, g, h, d] * k[b, t, h, d]
    s = lax.dot_general(qs, k, (((3,), (3,)), ((0, 2), (0, 2))),
                        precision=prec,
                        preferred_element_type=jnp.float32)
    t_ids = lax.broadcasted_iota(jnp.int32, s.shape, 3)
    s = jnp.where(t_ids < seq_lens[:, None, :, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    # o[b, h, g, d] = sum_t p[b, h, g, t] * v[b, t, h, d]
    o = lax.dot_general(p, v, (((3,), (1,)), ((0, 1), (0, 2))),
                        precision=prec,
                        preferred_element_type=jnp.float32)
    return (o / l).transpose(0, 2, 1, 3).astype(q.dtype)
