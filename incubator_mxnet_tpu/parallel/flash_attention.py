"""Flash attention as a Pallas TPU kernel.

The hot op the reference implements as fused CUDA matmuls
(src/operator/contrib/transformer.cc interleaved-matmul attention) —
here a real blocked online-softmax kernel: one grid instance per
(batch*head, q_block), K/V streamed block-by-block from VMEM with running
(max, sumexp, acc) statistics, so the full (Tq, Tk) score matrix never
materializes in HBM. O(T) memory instead of O(T^2), the standard
flash-attention recurrence (Dao et al.; same math as
ring_attention._block_attn).

Public entry `flash_attention(q, k, v, causal, sm_scale)` uses the
reference layout (B, T, H, D) and falls back to `attention_reference`
when the shape doesn't tile (tiny heads / ragged lengths). Off-TPU the
kernel runs in Pallas interpret mode, so the same code path is tested on
the CPU mesh.

Backward: REAL flash backward kernels (custom_vjp) — the forward also
emits the per-row log-sum-exp; `_fa_bwd_dq_kernel` streams k/v blocks
accumulating dq, `_fa_bwd_dkv_kernel` streams q blocks accumulating
dk/dv, both recomputing p from the saved lse with bf16 matmuls and f32
accumulation. O(block * T) memory end to end, which is what makes
LONG-CONTEXT TRAINING possible on one chip: T=8,192 trains at 8.0k tok/s
and T=16,384 at 3.8k tok/s on v5e where the XLA attention path cannot
even compile (docs/perf_notes.md). An XLA lax.scan fallback covers
untileable shapes and the no-pallas path.
"""
from __future__ import annotations

import functools
import logging
import threading

import jax
import jax.numpy as jnp

__all__ = ["flash_attention", "flash_attention_bh", "pallas_available",
           "dispatch_stats"]

_NEG_INF = -1e30


def _prec(dtype):
    """In-kernel dot precision: bf16 operands MUST say DEFAULT (Mosaic
    rejects the ambient contract_precision<fp32>); f32 operands want
    HIGHEST — DEFAULT would demote them to bf16 on the MXU (measured
    3.6e-3 abs divergence vs the f32 reference on the real chip)."""
    import jax.numpy as _jnp
    from jax import lax as _lax
    return (_lax.Precision.DEFAULT if dtype == _jnp.bfloat16
            else _lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=1)
def pallas_available():
    try:
        from jax.experimental import pallas  # noqa: F401
        from jax.experimental.pallas import tpu  # noqa: F401
        return True
    except Exception:
        return False


def _causal_mask(s, q_off, k_off, transposed=False):
    """Mask `s` to the causal (q_row >= k_row) region. s is
    (block_q, block_k), or (block_k, block_q) when transposed."""
    from jax import lax
    shape = s.shape
    a = lax.broadcasted_iota(jnp.int32, shape, 0)
    b = lax.broadcasted_iota(jnp.int32, shape, 1)
    if transposed:                       # rows are k, cols are q
        keep = (q_off + b) >= (k_off + a)
    else:                                # rows are q, cols are k
        keep = (q_off + a) >= (k_off + b)
    return jnp.where(keep, s, _NEG_INF)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
               block_q, block_k, causal, sm_scale):
    """One (batch*head, q_block, kv_block) grid step. The kv axis is the
    innermost ('arbitrary') grid dimension, so Pallas double-buffers the
    K/V block DMAs while this step computes; running (max, sumexp, acc)
    stats live in VMEM scratch that persists across kv steps.

    Refs: q (1, block_q, d) | kt (1, d, block_k) | v (1, block_k, d)
    | o (1, block_q, d); scratch m,l (block_q, 128) acc (block_q, d)."""
    from jax import lax
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    n_k = pl.num_programs(2)
    iq = pl.program_id(1)
    q_offset = iq * block_q

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # causal: a kv block strictly above the diagonal contributes nothing
    run = (j * block_k <= q_offset + block_q - 1) if causal else (j < n_k)

    @pl.when(run)
    def _step():
        # bf16 operands keep full MXU rate with f32 accumulation via
        # preferred_element_type; precision comes from _prec (DEFAULT for
        # bf16 — Mosaic requires it — HIGHEST for f32 inputs)
        prec = _prec(q_ref.dtype)
        q = q_ref[0] * jnp.asarray(sm_scale, q_ref.dtype)
        kt = k_ref[0]                      # (d, block_k), pre-transposed
        v = v_ref[0]                       # (block_k, d)
        s = lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                            precision=prec,
                            preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_offset, j * block_k)
        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, 0] = l_sc[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_sc[:] = acc_sc[:] * alpha[:, None] + lax.dot(
            p.astype(v.dtype), v, precision=prec,
            preferred_element_type=jnp.float32)
        m_sc[:, 0] = m_new

    @pl.when(j == n_k - 1)
    def _finish():
        l = l_sc[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> zeros
        o_ref[0] = (acc_sc[:] / l[:, None]).astype(o_ref.dtype)
        # row log-sum-exp for the backward kernels; fully-masked rows get
        # +inf-ish so exp(s - lse) underflows to 0 there
        lse_ref[0] = jnp.where(l_sc[:, 0] == 0.0, 1e30,
                               m_sc[:, 0] + jnp.log(l))[:, None]


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _interpret():
    return jax.default_backend() != "tpu"


def _to_bh(x):
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _un_bh(x, B, H, T, D):
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _fa_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    """q,k,v: (BH, T, D). Returns (out, lse) with lse the per-row
    log-sum-exp (BH, T, 1) f32 the backward kernels consume."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk = k.shape[1]
    kt = k.transpose(0, 2, 1)   # (BH, D, Tk) for the kernel's matmul
    grid = (bh, tq // block_q, tk // block_k)
    kern = functools.partial(_fa_kernel, block_q=block_q, block_k=block_k,
                             causal=causal, sm_scale=sm_scale)
    params = _compiler_params()
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, d, block_k), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # trailing singleton: TPU block rules need the last two dims
            # (block, 1) == (divisible-by-8, full-dim)
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sumexp
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_fwd",
    )(q, kt, v)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, out_ref,
                      dlse_ref, dq_ref, delta_ref, acc_sc, delta_sc, *,
                      block_q, block_k, causal, sm_scale):
    """dq for one q block, streaming k/v blocks (innermost grid dim):
      delta = rowsum(dO * O) - dlse   (computed HERE at j==0 — fused, so
                                 no separate XLA pass re-reads dO and O;
                                 dlse is the cotangent of the emitted
                                 lse — d lse/d s = p, so it enters ds
                                 with the OPPOSITE sign of delta. Zero
                                 for plain attention; nonzero when the
                                 ring-attention merge consumes lse.)
      p  = exp(s*scale - lse);  dp = dO V^T
      ds = p * (dp - delta);    dq = scale * sum_k ds K
    Matmuls keep input-dtype operands with f32 accumulation. delta is
    also emitted as an output for the dk/dv kernel to consume."""
    from jax import lax
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_off = pl.program_id(1) * block_q

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        d = jnp.sum(do_ref[0].astype(jnp.float32)
                    * out_ref[0].astype(jnp.float32), axis=-1,
                    keepdims=True) - dlse_ref[0]
        delta_sc[:] = jnp.broadcast_to(d, delta_sc.shape)
        delta_ref[0] = d

    run = (j * block_k <= q_off + block_q - 1) if causal else (j < n_k)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        # scale q in the INPUT dtype before the dot, exactly like the
        # forward — a post-dot f32 scale would recompute a subtly
        # different s than the one that produced the saved lse
        prec = _prec(q_ref.dtype)
        qs = q * jnp.asarray(sm_scale, q.dtype)
        s = lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                            precision=prec,
                            preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_off, j * block_k)
        p = jnp.exp(s - lse_ref[0])
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             precision=prec,
                            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_sc[:, :1])
        acc_sc[:] += lax.dot_general(ds.astype(k.dtype), k,
                                     (((1,), (0,)), ((), ())),
                                     precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _finish():
        dq_ref[0] = (acc_sc[:] * sm_scale).astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_sc, dv_sc, *, block_q, block_k,
                       causal, sm_scale):
    """dk/dv for one k block, streaming q blocks (innermost grid dim):
      p^T  = exp(s^T*scale - lse);     dv = sum_q p^T dO
      ds^T = p^T * (dp^T - delta);     dk = scale * sum_q ds^T Q"""
    from jax import lax
    from jax.experimental import pallas as pl

    i = pl.program_id(2)
    n_q = pl.num_programs(2)
    k_off = pl.program_id(1) * block_k
    q_off = i * block_q

    @pl.when(i == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    run = (q_off + block_q - 1 >= k_off) if causal else (i < n_q)

    @pl.when(run)
    def _step():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        prec = _prec(q_ref.dtype)
        qs = q * jnp.asarray(sm_scale, q.dtype)   # match the forward
        st = lax.dot_general(k, qs, (((1,), (1,)), ((), ())),
                             precision=prec,
                             preferred_element_type=jnp.float32)
        if causal:
            st = _causal_mask(st, q_off, k_off, transposed=True)
        pt = jnp.exp(st - lse_ref[0][:, 0][None, :])
        dv_sc[:] += lax.dot_general(pt.astype(do.dtype), do,
                                    (((1,), (0,)), ((), ())),
                                    precision=prec,
                            preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                              precision=prec,
                            preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0][:, 0][None, :])
        dk_sc[:] += lax.dot_general(dst.astype(q.dtype), q,
                                    (((1,), (0,)), ((), ())),
                                    precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _finish():
        dk_ref[0] = (dk_sc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _fa_backward(q, k, v, do, lse, out, dlse, causal, sm_scale, block_q,
                 block_k, interpret):
    """q,k,v,do,out: (BH, T, D); lse: (BH, Tq, 1) f32. Returns
    (dq, dk, dv) via the two flash backward kernels — O(block * T)
    memory, scores recomputed from the saved lse. delta = rowsum(dO*O)
    is computed INSIDE the dq kernel (per q block, at its first kv step)
    and handed to the dk/dv kernel as a (BH, Tq, 1) output — one fewer
    full pass over dO and O than a separate XLA delta computation."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk = k.shape[1]
    params = _compiler_params()

    dq, delta = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, causal=causal,
                          sm_scale=sm_scale),
        grid=(bh, tq // block_q, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, out, dlse)

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, causal=causal,
                          sm_scale=sm_scale),
        grid=(bh, tk // block_k, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, tk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(k, v, q, do, lse, delta)
    return dq, dk, dv


def _pick_block(t, preferred=1024):
    """Kernel block for an axis of length `t`, under the TPU tiling rule:
    the last two dims of every block are multiples of (8, 128) or span
    the array. The k block is the LANE dim of the pre-transposed key, so
    a partial block is a multiple of 128; an axis that fits in
    `preferred` is taken whole (rows in multiples of 8). None = no legal
    block, the caller takes the XLA path."""
    if t % 8:
        return None
    if t <= preferred:
        return t
    for b in (preferred, 512, 256, 128):
        if t % b == 0:
            return b
    return None


# Which implementation each traced call got, by reason. Attention drops
# to the O(T^2) XLA reference when no block fits; that must be a choice
# somebody can see, not a silent one (chip_smoke.py asserts on it).
_dispatch = {"pallas": 0, "reference": 0}
_dispatch_lock = threading.Lock()


def dispatch_stats():
    """{"pallas": n, "reference": n}: traced flash_attention/flash_hop
    calls served by the Pallas kernels vs dropped to attention_reference."""
    with _dispatch_lock:
        return dict(_dispatch)


def _blocks_for(tq, tk, d):
    """(block_q, block_k) when the Pallas kernels take this shape, else
    None — counted and logged either way."""
    bq, bk = _pick_block(tq), _pick_block(tk)
    ok = pallas_available() and bq is not None and bk is not None \
        and d % 8 == 0
    with _dispatch_lock:
        _dispatch["pallas" if ok else "reference"] += 1
    if not ok:
        logging.warning(
            "flash_attention: Tq=%d Tk=%d D=%d has no legal kernel block "
            "(blocks %s/%s); using the O(T^2) attention_reference",
            tq, tk, d, bq, bk)
        return None
    return bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, sm_scale):
    return _flash_fwd_impl(q, k, v, causal, sm_scale)


def _flash_fwd_impl(q, k, v, causal, sm_scale, want_lse=False):
    from .ring_attention import attention_reference

    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    # v5e-tuned r4: (1024, 1024) — 33.8 TF/s fwd at T=2048 (vs 30.5 at
    # the r3 (512,1024) tune) and 53.4 at T=8192 (vs 46.6); the r3 sweep
    # predates the backward/block interplay (docs/perf_notes.md)
    blocks = _blocks_for(Tq, Tk, D)
    if blocks is None:
        out = attention_reference(q, k, v, causal=causal,
                                  sm_scale=sm_scale)
        return (out, None) if want_lse else out
    bq, bk = blocks
    out, lse = _fa_forward(_to_bh(q), _to_bh(k), _to_bh(v), causal,
                           sm_scale, bq, bk, _interpret())
    out = _un_bh(out, B, H, Tq, D)
    return (out, lse) if want_lse else out


def _flash_vjp_fwd(q, k, v, causal, sm_scale):
    out, lse = _flash_fwd_impl(q, k, v, causal, sm_scale, want_lse=True)
    # the scan fallback recomputes everything from q/k/v — keeping `out`
    # alive would cost an activation-sized residual for nothing
    return out, (q, k, v, out if lse is not None else None, lse)


def _flash_vjp_bwd(causal, sm_scale, res, g):
    """Backward. With a Pallas forward (saved lse) the two flash backward
    KERNELS run (dq streams k/v blocks; dk/dv streams q blocks) — O(block
    * T) memory, bf16 matmuls, f32 accumulation. Fallback (no pallas /
    untileable): an XLA lax.scan over q blocks with the same recompute
    math."""
    from jax import lax
    q, k, v, out, lse = res
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if lse is not None:
        # v5e block sweep (docs/perf_notes.md round 4): (1024,1024) runs
        # the backward pair at 34.3 TF/s vs 28.9 at the old (512,512)
        bq = _pick_block(Tq)
        bk = _pick_block(Tk)
        do_bh = _to_bh(g)
        dq, dk, dv = _fa_backward(_to_bh(q), _to_bh(k), _to_bh(v), do_bh,
                                  lse, _to_bh(out),
                                  jnp.zeros_like(lse), causal, sm_scale,
                                  bq, bk, _interpret())
        return (_un_bh(dq, B, H, Tq, D), _un_bh(dk, B, H, Tk, D),
                _un_bh(dv, B, H, Tk, D))
    bq = _pick_block(Tq, 256)
    if bq is None or bq == Tq:
        # tiny/ragged: dense vjp of the reference is fine at this size
        from .ring_attention import attention_reference
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(
                q_, k_, v_, causal=causal, sm_scale=sm_scale), q, k, v)
        return vjp(g)

    f32 = jnp.float32
    n = Tq // bq
    qs = q.reshape(B, n, bq, H, D).transpose(1, 0, 2, 3, 4)
    gs = g.reshape(B, n, bq, H, D).transpose(1, 0, 2, 3, 4)
    cols = jnp.arange(Tk)
    # matmul operands stay in the INPUT dtype (bf16 = full MXU rate; fp32
    # operands force multi-pass emulation) with f32 accumulation via
    # preferred_element_type; only the softmax/rescale math runs f32 —
    # the same precision split as the forward Pallas kernel
    ein = functools.partial(jnp.einsum, preferred_element_type=f32,
                            precision=_prec(q.dtype))

    def step(carry, inp):
        dk, dv = carry
        i, qb, gb = inp
        s = ein("bqhd,bkhd->bhqk", qb, k) * sm_scale
        if causal:
            rows = i * bq + jnp.arange(bq)
            s = jnp.where((rows[:, None] >= cols[None, :])[None, None],
                          s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        pc = p.astype(q.dtype)
        dv_new = dv + ein("bhqk,bqhd->bkhd", pc, gb)
        dp = ein("bqhd,bkhd->bhqk", gb, v)
        delta = jnp.sum(dp * p, axis=-1, keepdims=True)
        ds = (p * (dp - delta)).astype(q.dtype)
        dqb = ein("bhqk,bkhd->bqhd", ds, k) * sm_scale
        dk_new = dk + ein("bhqk,bqhd->bkhd", ds, qb) * sm_scale
        return (dk_new, dv_new), dqb

    (dk, dv), dqs = lax.scan(
        step, (jnp.zeros((B, Tk, H, D), f32), jnp.zeros((B, Tk, H, D), f32)),
        (jnp.arange(n), qs, gs))
    dq = dqs.transpose(1, 0, 2, 3, 4).reshape(B, Tq, H, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None):
    """Blocked flash attention. q,k,v: (B, T, H, D) (the layout of
    attention_reference / the transformer flagship). Differentiable."""
    if sm_scale is None:
        import math
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash(q, k, v, bool(causal), float(sm_scale))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_hop(q, k, v, causal, sm_scale):
    """(out, lse) pair for ONE ring-attention hop, differentiable in
    BOTH outputs: the backward folds the lse cotangent into the kernels'
    delta term (d lse/d s = p). q,k,v: (B, t, H, D); lse out: (B, H, t)
    f32 with -inf on fully-masked rows."""
    return _flash_hop_fwd_impl(q, k, v, causal, sm_scale)


def _flash_hop_fwd_impl(q, k, v, causal, sm_scale):
    B, T, H, D = q.shape
    bq = _pick_block(T)
    bk = _pick_block(k.shape[1])
    out, lse = _fa_forward(_to_bh(q), _to_bh(k), _to_bh(v), causal,
                           sm_scale, bq, bk, _interpret())
    lse_bht = lse.reshape(B, H, T)
    lse_bht = jnp.where(lse_bht >= 1e29, -jnp.inf, lse_bht)
    return (_un_bh(out, B, H, T, D).astype(jnp.float32), lse_bht)


def _flash_hop_vjp_fwd(q, k, v, causal, sm_scale):
    out, lse = _flash_hop_fwd_impl(q, k, v, causal, sm_scale)
    return (out, lse), (q, k, v, out, lse)


def _flash_hop_vjp_bwd(causal, sm_scale, res, cts):
    g_out, g_lse = cts
    q, k, v, out, lse = res
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq = _pick_block(Tq)
    bk = _pick_block(Tk)
    lse_kern = jnp.where(jnp.isfinite(lse), lse, 1e30).reshape(
        B * H, Tq, 1).astype(jnp.float32)
    dlse = g_lse.reshape(B * H, Tq, 1).astype(jnp.float32)
    dq, dk, dv = _fa_backward(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(g_out.astype(q.dtype)),
        lse_kern, _to_bh(out.astype(q.dtype)), dlse, causal, sm_scale,
        bq, bk, _interpret())
    return (_un_bh(dq, B, H, Tq, D).astype(q.dtype),
            _un_bh(dk, B, H, Tk, D).astype(k.dtype),
            _un_bh(dv, B, H, Tk, D).astype(v.dtype))


flash_hop.defvjp(_flash_hop_vjp_fwd, _flash_hop_vjp_bwd)


def flash_attention_bh(q, k, v, causal=False, sm_scale=None):
    """(BH, T, D)-layout flash attention for callers that already hold
    merged batch*head arrays: a singleton-head view of flash_attention
    (the (BH,T,1,D) reshape is free), so it shares the kernels, the
    custom vjp, AND the O(block*T) scan fallback. Note: routing the
    transformer through this entry to skip its _to_bh copies was
    measured 4.4% SLOWER end to end (docs/perf_notes.md round-4
    addendum) — the model keeps the standard layout; this entry is for
    code that genuinely starts from (BH,T,D)."""
    return flash_attention(q[:, :, None, :], k[:, :, None, :],
                           v[:, :, None, :], causal=causal,
                           sm_scale=sm_scale)[:, :, 0, :]
