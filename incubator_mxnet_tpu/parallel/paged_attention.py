"""Ragged paged attention for autoregressive decode (Pallas TPU kernel).

The serving-side sibling of flash_attention.py, following "Ragged Paged
Attention" (arXiv:2604.15464): at decode time every sequence in the
batch has a DIFFERENT context length, and its KV history lives in
fixed-size pages scattered across a shared pool rather than one
contiguous (B, T_max, H, D) buffer. Attention therefore reads through a
per-sequence page table — the kernel's grid walks (sequence, page) and
uses SCALAR-PREFETCHED page-table entries in the BlockSpec index maps,
so each grid step DMAs exactly the one (page_size, H, D) page the
sequence actually owns (the ragged gather XLA would otherwise
materialize as a (B, T_max, H, D) copy per step).

Layouts::

    q          (B, H, D)        one query token per active sequence
    k_pages    (P, page_size, H, D)   the shared KV pool (keys)
    v_pages    (P, page_size, H, D)   the shared KV pool (values)
    page_table (B, max_pages)   int32 page ids, row-major per sequence
    seq_lens   (B,) int32       valid context length per sequence

Contract: positions ``t < seq_lens[b]`` of sequence ``b`` live at pool
row ``page_table[b, t // page_size] * page_size + t % page_size``.
``seq_lens`` values below 1 are CLAMPED to 1 (an idle batch slot still
attends to exactly one — arbitrary — key, so its output is finite and
both implementations agree bit-for-bit on garbage rows; callers ignore
idle-slot outputs).

Dispatch goes through ``tune.tuned_call`` with the XLA gather
composition as the implicit reference candidate: the Pallas kernel is
parity-checked against it before it can ever win (losing or diverging
kernels are unreachable by construction), and off-TPU the kernel is only
offered in interpret mode under ``MXTPU_TUNE_INTERPRET`` — which is how
CPU tier-1 exercises the exact kernel code path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .flash_attention import _prec, pallas_available

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_pallas", "paged_attention_multiquery",
           "paged_attention_mq_reference", "paged_attention_mq_pallas",
           "register_kernels"]

_NEG_INF = -1e30


def _interpret():
    return jax.default_backend() != "tpu"


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    # batch axis is parallel; the page axis accumulates running
    # softmax statistics, so it must stay "arbitrary" (sequential)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _scale(sm_scale, d):
    return sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)


# ---------------------------------------------------------------------------
# XLA reference (the implicit "xla" candidate — always available)
# ---------------------------------------------------------------------------

def paged_attention_reference(q, k_pages, v_pages, page_table, seq_lens,
                              *, sm_scale=None):
    """Gather-based composition: materialize each sequence's pages into
    a dense (B, max_pages*page_size, H, D) view and run masked softmax
    attention. O(B * T_max) memory per step — exactly the copy the
    paged kernel exists to avoid — but always correct on every backend,
    which makes it the numerical reference the kernel must match."""
    from jax import lax
    B, H, D = q.shape
    page_size = k_pages.shape[1]
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


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _page_update(q, k, v, seq_len, j, page_size, m_prev, l_prev, acc_prev):
    """Fold one (page_size, H, D) page into one query's running softmax.

    Everything stays in the page's own (rows, H, D) layout — heads on
    sublanes, head_dim on lanes — and runs on the VPU: a decode query is
    one row per head, far too thin for the MXU, and Mosaic takes neither
    a dot batched over a non-leading axis nor the transposes that would
    make H lead. Scores and the per-head statistics m/l are carried
    broadcast along the lane axis, shaped like the page and the
    accumulator, so no step needs a relayout. q (H, D) f32 pre-scaled; k, v (page_size, H, D); returns
    the new (m, l, acc), each (H, D) f32."""
    from jax import lax
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    # s[p, h, :] = sum_d q[h, d] * k[p, h, d], the same value on every lane
    s = jnp.broadcast_to(jnp.sum(q[None] * k, axis=-1, keepdims=True),
                         k.shape)
    pos = j * page_size + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    s = jnp.where(pos < seq_len, s, _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
    p = jnp.exp(s - m_new[None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=0)
    acc_new = acc_prev * alpha + jnp.sum(p * v, axis=0)
    return m_new, l_new, acc_new


def _pa_kernel(pt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
               m_sc, l_sc, acc_sc, *, page_size, sm_scale):
    """One (sequence b, page j) grid step. The page axis is innermost
    ('arbitrary'), so Pallas double-buffers the next page's DMA while
    this one computes; running (max, sumexp, acc) live in VMEM scratch
    that persists across the page walk — the flash_attention recurrence
    over pages instead of contiguous kv blocks.

    Refs: q (1, H, D) | k, v (1, page_size, H, D) — the ONE pool page
    pt_ref[b, j] selected by the scalar-prefetched index map — | o
    (1, H, D); scratch m, l, acc (H, D), all f32."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)
    seq_len = jnp.maximum(sl_ref[b], 1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # a page past the sequence's tail contributes nothing: skip it (and
    # its statistics update) entirely — this is where raggedness wins
    @pl.when(j * page_size < seq_len)
    def _step():
        # scaled in the INPUT dtype, like the reference
        q = (q_ref[0] * jnp.asarray(sm_scale, q_ref.dtype)) \
            .astype(jnp.float32)
        m_sc[...], l_sc[...], acc_sc[...] = _page_update(
            q, k_ref[0], v_ref[0], seq_len, j, page_size,
            m_sc[...], l_sc[...], acc_sc[...])

    @pl.when(j == n_j - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, page_table, seq_lens,
                           *, sm_scale=None, interpret=None):
    """Invoke the ragged kernel: grid (B, max_pages), page_table and
    seq_lens scalar-prefetched so the k/v BlockSpec index maps can steer
    each step's DMA at the sequence's j-th OWNED page."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    if interpret is None:
        interpret = _interpret()
    scale = _scale(sm_scale, D)
    seq_lens = jnp.maximum(seq_lens.astype(jnp.int32), 1)
    page_table = page_table.astype(jnp.int32)

    kernel = functools.partial(_pa_kernel, page_size=page_size,
                               sm_scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, pt, sl: (b, 0, 0)),
            pl.BlockSpec((1, page_size, H, D),
                         lambda b, j, pt, sl: (pt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, page_size, H, D),
                         lambda b, j, pt, sl: (pt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, pt, sl: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, D), jnp.float32)] * 3,
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )
    return call(page_table, seq_lens, q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# multi-query variant (speculative-decode verify read path)
# ---------------------------------------------------------------------------
#
# Verify scores G = k+1 positions of every sequence in ONE step, so each
# sequence contributes a BLOCK of G query tokens instead of one, and each
# query attends to a different-length prefix of the same page walk::
#
#     q          (B, G, H, D)     G stacked query tokens per sequence
#     seq_lens   (B, G) int32     context length per (sequence, query)
#
# Everything else (pool layout, page-table indirection, clamp-to-1 on
# idle rows) is identical to the single-query contract above. The page
# walk is shared: one DMA per owned page serves all G queries, which is
# the whole point — verify costs one pass over the KV history, not G.


def paged_attention_mq_reference(q, k_pages, v_pages, page_table, seq_lens,
                                 *, sm_scale=None):
    """Gather-based multi-query composition: dense per-sequence view,
    per-(sequence, query) masked softmax. The numerical reference the
    mq kernel must match before it can win."""
    from jax import lax
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


def _pa_mq_kernel(pt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_sc, l_sc, acc_sc, *, page_size, sm_scale):
    """One (sequence b, page j) grid step of multi-query verify. Same
    double-buffered page walk as _pa_kernel, but the flash recurrence
    carries a G axis: each of the sequence's G query tokens keeps its
    own (max, sumexp, acc) and its own length mask, all fed by the ONE
    page this step DMA'd. G is small and static (k+1 speculation
    positions), so the queries are a python loop over _page_update and
    each length is one scalar SMEM read.

    Refs: q (1, G, H, D) | k, v (1, page_size, H, D) | o (1, G, H, D);
    scratch m, l, acc (G, H, D), all f32."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)
    n_g = q_ref.shape[1]
    sl = [jnp.maximum(sl_ref[b, g], 1) for g in range(n_g)]
    longest = functools.reduce(jnp.maximum, sl)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # skip pages past the LONGEST query's tail; shorter queries inside
    # the page are handled by the per-query mask
    @pl.when(j * page_size < longest)
    def _step():
        k = k_ref[0]
        v = v_ref[0]
        for g in range(n_g):
            q = (q_ref[0, g] * jnp.asarray(sm_scale, q_ref.dtype)) \
                .astype(jnp.float32)
            m_sc[g], l_sc[g], acc_sc[g] = _page_update(
                q, k, v, sl[g], j, page_size, m_sc[g], l_sc[g], acc_sc[g])

    @pl.when(j == n_j - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def paged_attention_mq_pallas(q, k_pages, v_pages, page_table, seq_lens,
                              *, sm_scale=None, interpret=None):
    """Invoke the multi-query ragged kernel: grid (B, max_pages), the
    (B, G) seq_lens matrix scalar-prefetched alongside the page table."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, G, H, D = q.shape
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    if interpret is None:
        interpret = _interpret()
    scale = _scale(sm_scale, D)
    seq_lens = jnp.maximum(seq_lens.astype(jnp.int32), 1)
    page_table = page_table.astype(jnp.int32)

    kernel = functools.partial(_pa_mq_kernel, page_size=page_size,
                               sm_scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, G, H, D), lambda b, j, pt, sl: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, H, D),
                         lambda b, j, pt, sl: (pt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, page_size, H, D),
                         lambda b, j, pt, sl: (pt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, H, D),
                               lambda b, j, pt, sl: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, H, D), jnp.float32)] * 3,
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, H, D), q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )
    return call(page_table, seq_lens, q, k_pages, v_pages)


def paged_attention_mq_candidates(args, kwargs):
    """tuned_call builder for the multi-query entry: shapes only."""
    from collections import OrderedDict
    cands = OrderedDict()
    if not _offer_candidates():
        return cands
    q, k_pages = args[0], args[1]
    if len(q.shape) != 4 or len(k_pages.shape) != 4:
        return cands
    cands["pallas"] = paged_attention_mq_pallas
    return cands


# ---------------------------------------------------------------------------
# autotuner registration + public entry
# ---------------------------------------------------------------------------

def _offer_candidates():
    """Pallas candidates race only where they can actually run: always
    on TPU; off-TPU only in interpret mode under MXTPU_TUNE_INTERPRET
    (the CPU tier-1 parity gate — fused_conv's discipline)."""
    from ..util import getenv_bool
    if not pallas_available():
        return False
    return not _interpret() or getenv_bool("MXTPU_TUNE_INTERPRET")


def paged_attention_candidates(args, kwargs):
    """tuned_call builder: shapes only (args may be tracers)."""
    from collections import OrderedDict
    cands = OrderedDict()
    if not _offer_candidates():
        return cands
    q, k_pages = args[0], args[1]
    if len(q.shape) != 3 or len(k_pages.shape) != 4:
        return cands
    cands["pallas"] = paged_attention_pallas
    return cands


def register_kernels():
    """Register the ragged paged-attention search space (runs at module
    import; idempotent — re-registering replaces the same-name spec)."""
    from .. import tune
    tune.register_kernel("paged_attention", paged_attention_candidates,
                         version=1)
    tune.register_kernel("paged_attention_mq", paged_attention_mq_candidates,
                         version=1)


register_kernels()


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    sm_scale=None):
    """Ragged paged attention over a shared KV page pool (see module
    docstring for layouts). Dispatches to the tuned winner for this
    (shape, dtype, device); the XLA gather composition is the implicit
    fallback and numerical reference."""
    from .. import tune
    return tune.tuned_call(
        "paged_attention", paged_attention_reference,
        q, k_pages, v_pages, page_table, seq_lens, sm_scale=sm_scale)


def paged_attention_multiquery(q, k_pages, v_pages, page_table, seq_lens,
                               sm_scale=None):
    """Multi-query ragged paged attention: q is (B, G, H, D) — G stacked
    query tokens per sequence — and seq_lens is (B, G), one context
    length per (sequence, query). The speculative-decode verify read
    path: one shared page walk scores all G positions of every sequence.
    Dispatches to the tuned winner; the XLA gather composition is the
    implicit fallback and numerical reference."""
    from .. import tune
    return tune.tuned_call(
        "paged_attention_mq", paged_attention_mq_reference,
        q, k_pages, v_pages, page_table, seq_lens, sm_scale=sm_scale)
