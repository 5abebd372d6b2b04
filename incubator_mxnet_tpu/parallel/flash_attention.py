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

Causal calls skip the masked half twice: the grid skips the blocks above
the diagonal, and a block ON the diagonal is walked in strips of 256 rows
that stop at the diagonal, with the mask on each strip's one 256 x 256
triangle (_causal_plan). At T <= 1,024 a head is one grid block, so the
walk is all the skipping there is.

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

import collections
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


# The kernels' bodies are written in lax, not jnp: a jnp function is a jit of
# its own, and tracing one costs five times what binding the primitive does.
# A train step traces and lowers three kernels a layer in every process, and
# a ref load is the dearest thing to lower, so each kernel loads its blocks
# once and cuts strips out of the values (PERF.md section 6, PR 26).

def _keep(shape, q_off, k_off, transposed=False):
    """Causal (q_row >= k_row) mask of a score block whose first row and
    column sit at q_off / k_off. shape is (q rows, k rows), or (k rows,
    q rows) when transposed."""
    from jax import lax
    a = lax.broadcasted_iota(jnp.int32, shape, 0)
    b = lax.broadcasted_iota(jnp.int32, shape, 1)
    if transposed:                       # rows are k, cols are q
        return (q_off + b) >= (k_off + a)
    return (q_off + a) >= (k_off + b)    # rows are q, cols are k


def _cut(x, rows=None, cols=None):
    """x[rows[0]:rows[1], cols[0]:cols[1]] of a 2-d value, None for all.
    Row bounds are multiples of 8 and column bounds of 128 wherever a
    kernel cuts, so a cut moves no data."""
    from jax import lax
    for axis, cut in ((0, rows), (1, cols)):
        if cut is not None and cut != (0, x.shape[axis]):
            x = lax.slice_in_dim(x, cut[0], cut[1], axis=axis)
    return x


def _stack(parts, axis=0):
    """The strips' results side by side again, in the order given."""
    from jax import lax
    return parts[0] if len(parts) == 1 else lax.concatenate(parts, axis)


def _masked(s, keep, lead=False):
    """s with -inf where `keep` is False. A `keep` narrower than s covers
    its trailing columns only (its leading ones with `lead`): the one
    sub-block of a strip that lies on the diagonal."""
    from jax import lax
    n, w = keep.shape[1], s.shape[1]
    on = _cut(s, cols=(0, n) if lead else (w - n, w))
    on = lax.select(keep, on, lax.full_like(on, _NEG_INF))
    if n == w:
        return on
    rest = _cut(s, cols=(n, w) if lead else (0, w - n))
    return _stack([on, rest] if lead else [rest, on], axis=1)


def _over(col, like):
    """A column (r, 1) spread over the lanes of `like` (r, w)."""
    from jax import lax
    return lax.broadcast_in_dim(col, like.shape, (0, 1))


def _under(col, like):
    """A column (w, 1) turned into a row of lanes and spread down the
    sublanes of `like` (r, w)."""
    from jax import lax
    row = lax.expand_dims(lax.squeeze(col, (1,)), (0,))
    return lax.broadcast_in_dim(row, like.shape, (0, 1))


def _dot(a, b, contract, prec):
    """f32 a . b contracting a's dim contract[0] with b's contract[1].
    bf16 operands keep full MXU rate with f32 accumulation; precision
    comes from _prec (DEFAULT for bf16 — Mosaic requires it — HIGHEST for
    f32 inputs)."""
    from jax import lax
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                           precision=prec,
                           preferred_element_type=jnp.float32)


# Edge of the score sub-blocks a grid block ON the diagonal is walked in, in
# all three kernels; a multiple of 128 (it cuts the LANE dim of the
# pre-transposed key). On the chip 128 runs the three kernels 6%, 3% and 7%
# faster than 256 at (BH, T, D) = (512, 1024, 64), and costs twice the strips
# to trace and lower in every process: at 24 layers that is 7 s of set-up for
# 1.2% of a GPT-2 medium step (PERF.md section 6, PR 26).
_SUB = 256


def _sub_block(block_q, block_k):
    """Edge of the sub-blocks a grid block is made of, two a side at the
    least: _SUB, 128 for the lengths that are not multiples of _SUB, or
    None."""
    for c in (_SUB, 128):
        if block_q % c == 0 and block_k % c == 0 \
                and min(block_q, block_k) >= 2 * c:
            return c
    return None


# What a causal call's kernels do, from what they can see (the two lengths
# and the block sizes): `sub`, the sub-block edge on the diagonal (None: one
# masked pass, as before PR 26); whether any grid block lies wholly `below`
# the diagonal (one unmasked pass) or `straddle`s it off the block's corner
# (Tq != Tk with unequal blocks: one masked pass); and the score sub-blocks
# computed, `run`, of `all` in the (Tq, Tk) square, in units of `sub` (of
# grid blocks where there is none).
_Plan = collections.namedtuple("_Plan", "sub below straddle run all")


def _walk_rows(block_q, block_k, sub):
    """[(rows, cols, on)]: the strips of an aligned diagonal grid block by
    q sub-block: q rows `rows` see k rows `cols`, from 0 up to the
    diagonal, the last sub-block of them through the triangle where `on`
    (a q sub-block past the last k sub-block sees them all, unmasked)."""
    n_k = block_k // sub
    return [((i * sub, (i + 1) * sub), (0, min(i + 1, n_k) * sub), i < n_k)
            for i in range(block_q // sub)]


def _causal_plan(tq, tk, block_q, block_k):
    """The _Plan of a causal call over (tq, tk) in (block_q, block_k) grid
    blocks."""
    sub = _sub_block(block_q, block_k)
    per_block = (block_q // sub) * (block_k // sub) if sub else 1
    on_diag = sum(cols[1] // sub for _, cols, _ in
                  _walk_rows(block_q, block_k, sub)) if sub else 1
    below = straddle = diag = 0
    for q0 in range(0, tq, block_q):
        for k0 in range(0, tk, block_k):
            if k0 > q0 + block_q - 1:
                continue                 # above: the grid test skips it
            if k0 + block_k - 1 <= q0:
                below += 1
            elif k0 == q0:
                diag += 1
            else:
                straddle += 1
    return _Plan(sub, below > 0, straddle > 0,
                 (below + straddle) * per_block + diag * on_diag,
                 (tq // block_q) * (tk // block_k) * per_block)


def _causal_branches(plan, q_off, k_off, block_q, block_k, full, walk):
    """Run the one of `full(masked)` / `walk()` that the grid block at
    (q_off, k_off) takes; a block wholly above the diagonal runs none."""
    from jax.experimental import pallas as pl
    below = k_off + block_k - 1 <= q_off
    diag = q_off == k_off
    if plan.below:
        pl.when(below)(lambda: full(False))
    pl.when(diag)(walk if plan.sub else (lambda: full(True)))
    if plan.straddle:
        reach = k_off <= q_off + block_q - 1
        pl.when(reach & ~below & ~diag)(lambda: full(True))


def _fwd_fold(carry, s, v, prec):
    """One online-softmax step: scores s (r, w) folded into carry = (max,
    sumexp, acc), columns (r, 1) and (r, d), with v (w, d). Without a
    carry s holds its rows' every score: the plain statistics, nothing to
    rescale."""
    from jax import lax
    m = lax.expand_dims(lax.reduce_max(s, (1,)), (1,))
    if carry:
        m_prev, l_prev, acc_prev = carry
        m = lax.max(m_prev, m)
    p = lax.exp(lax.sub(s, _over(m, s)))
    l = lax.expand_dims(lax.reduce_sum(p, (1,)), (1,))
    acc = _dot(lax.convert_element_type(p, v.dtype), v, (1, 0), prec)
    if carry:
        alpha = lax.exp(lax.sub(m_prev, m))
        l = lax.add(lax.mul(l_prev, alpha), l)
        acc = lax.add(lax.mul(acc_prev, _over(alpha, acc)), acc)
    return m, l, acc


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, block_q,
               block_k, plan, sm_scale):
    """One (batch*head, q_block, kv_block) grid step. The kv axis is the
    innermost ('arbitrary') grid dimension, so Pallas double-buffers the
    K/V block DMAs while this step computes; running (max, sumexp, acc)
    stats live in VMEM scratch that persists across kv steps. `plan` is
    None for a non-causal call (every block one unmasked pass), else the
    call's _causal_plan. Without scratch the grid step is a head's only
    one and is walked in strips that each hold their rows' every score:
    the statistics go straight to the output.

    Refs: q (1, block_q, d) | kt (1, d, block_k) | v (1, block_k, d)
    | o (1, block_q, d); scratch m,l (block_q, 128) acc (block_q, d)."""
    from jax import lax
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_off = pl.program_id(1) * block_q
    k_off = j * block_k
    prec = _prec(q_ref.dtype)
    if scratch:
        m_sc, l_sc, acc_sc = scratch

        @pl.when(j == 0)
        def _init():
            m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)
            acc_sc[:] = jnp.zeros_like(acc_sc)

    def emit(m, l, acc):
        some = lax.select(l == 0.0, lax.full_like(l, 1.0), l)
        # fully-masked rows: zeros out, and a +inf-ish log-sum-exp so that
        # exp(s - lse) underflows to 0 in the backward kernels
        o_ref[0] = (acc / _over(some, acc)).astype(o_ref.dtype)
        lse_ref[0] = lax.select(l == 0.0, lax.full_like(l, 1e30),
                                m + lax.log(some))

    def fold(strips):
        """Fold k columns `cols`, masked by `keep`, into the running stats
        of q rows `rows`, for each (rows, cols, keep) of `strips`, which
        together hold every q row once: every strip's scores first, so
        that no strip's first matmul queues behind another's second on
        its MXU."""
        q = q_ref[0] * jnp.asarray(sm_scale, q_ref.dtype)
        kt, v = k_ref[0], v_ref[0]
        old = (m_sc[:, :1], l_sc[:, :1], acc_sc[:]) if scratch else None
        scores = [_dot(_cut(q, rows), _cut(kt, cols=cols), (1, 0), prec)
                  for rows, cols, _ in strips]
        new = [_fwd_fold(old and [_cut(x, rows) for x in old],
                         s if keep is None else _masked(s, keep),
                         _cut(v, cols), prec)
               for (rows, cols, keep), s in zip(strips, scores)]
        m, l, acc = (_stack(list(x)) for x in zip(*new))
        if scratch:
            m_sc[:, :1], l_sc[:, :1], acc_sc[:] = m, l, acc
        else:
            emit(m, l, acc)

    def full(masked):
        fold([((0, block_q), (0, block_k),
               _keep((block_q, block_k), q_off, k_off) if masked else None)])

    def walk():
        c = plan.sub
        tri = _keep((c, c), 0, 0)
        fold([(rows, cols, tri if on else None)
              for rows, cols, on in _walk_rows(block_q, block_k, c)])

    if plan is None:
        full(False)
    else:
        _causal_branches(plan, q_off, k_off, block_q, block_k, full, walk)

    if scratch:
        @pl.when(j == n_k - 1)
        def _finish():
            emit(m_sc[:, :1], l_sc[:, :1], acc_sc[:])


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
    plan = _causal_plan(tq, tk, block_q, block_k) if causal else None
    if causal:
        with _dispatch_lock:
            _dispatch["causal_subblocks_run"] += plan.run
            _dispatch["causal_subblocks_all"] += plan.all
    kern = functools.partial(_fa_kernel, block_q=block_q, block_k=block_k,
                             plan=plan, sm_scale=sm_scale)
    # a head that is one grid block walked in strips carries no running
    # statistics from one kv step to the next: no scratch
    alone = causal and plan.sub and grid[1:] == (1, 1)
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
        scratch_shapes=[] if alone else [
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
                      block_q, block_k, plan, sm_scale):
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
    also emitted as an output for the dk/dv kernel to consume. `plan` as
    in _fa_kernel."""
    from jax import lax
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_off = pl.program_id(1) * block_q
    k_off = j * block_k
    prec = _prec(q_ref.dtype)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        d = jnp.sum(do_ref[0].astype(jnp.float32)
                    * out_ref[0].astype(jnp.float32), axis=-1,
                    keepdims=True) - dlse_ref[0]
        delta_sc[:] = jnp.broadcast_to(d, delta_sc.shape)
        delta_ref[0] = d

    def add(strips):
        """Add to the dq of q rows `rows` what k rows `cols`, masked by
        `keep`, give, for each (rows, cols, keep) of `strips`, which
        together hold every q row once: every strip's two score matmuls
        first, so that none queues behind another strip's last."""
        # scale q in the INPUT dtype before the dot, exactly like the
        # forward — a post-dot f32 scale would recompute a subtly
        # different s than the one that produced the saved lse
        qs = q_ref[0] * jnp.asarray(sm_scale, q_ref.dtype)
        k, v, do = k_ref[0], v_ref[0], do_ref[0]
        lse, delta = lse_ref[0], delta_sc[:, :1]
        first = [(_dot(_cut(qs, rows), _cut(k, cols), (1, 1), prec),
                  _dot(_cut(do, rows), _cut(v, cols), (1, 1), prec))
                 for rows, cols, _ in strips]
        parts = []
        for (rows, cols, keep), (s, dp) in zip(strips, first):
            if keep is not None:
                s = _masked(s, keep)
            ds = lax.mul(lax.exp(lax.sub(s, _over(_cut(lse, rows), s))),
                         lax.sub(dp, _over(_cut(delta, rows), dp)))
            parts.append(_dot(lax.convert_element_type(ds, k.dtype),
                              _cut(k, cols), (1, 0), prec))
        acc_sc[:] += _stack(parts)

    def full(masked):
        add([((0, block_q), (0, block_k),
              _keep((block_q, block_k), q_off, k_off) if masked else None)])

    def walk():
        c = plan.sub
        tri = _keep((c, c), 0, 0)
        add([(rows, cols, tri if on else None)
             for rows, cols, on in _walk_rows(block_q, block_k, c)])

    if plan is None:
        full(False)
    else:
        _causal_branches(plan, q_off, k_off, block_q, block_k, full, walk)

    @pl.when(j == n_k - 1)
    def _finish():
        dq_ref[0] = (acc_sc[:] * sm_scale).astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_sc, dv_sc, *, block_q, block_k,
                       plan, sm_scale):
    """dk/dv for one k block, streaming q blocks (innermost grid dim):
      p^T  = exp(s^T*scale - lse);     dv = sum_q p^T dO
      ds^T = p^T * (dp^T - delta);     dk = scale * sum_q ds^T Q
    `plan` as in _fa_kernel; the walk goes by k sub-block here, over the
    q sub-blocks at and below the diagonal."""
    from jax import lax
    from jax.experimental import pallas as pl

    i = pl.program_id(2)
    n_q = pl.num_programs(2)
    k_off = pl.program_id(1) * block_k
    q_off = i * block_q
    prec = _prec(q_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def add(strips):
        """Add to the dk, dv of k rows `cols` what q rows `rows`, masked by
        `keep`, give, for each (cols, rows, keep) of `strips`, in
        transposed scores (k rows, q rows); the score matmuls first, as in
        the dq kernel. Strips that leave k rows out leave their dk, dv as
        they are."""
        q, do, k, v = q_ref[0], do_ref[0], k_ref[0], v_ref[0]
        qs = q * jnp.asarray(sm_scale, q.dtype)      # as the forward
        lse, delta = lse_ref[0], delta_ref[0]
        first = [(_dot(_cut(k, cols), _cut(qs, rows), (1, 1), prec),
                  _dot(_cut(v, cols), _cut(do, rows), (1, 1), prec))
                 for cols, rows, _ in strips]
        dks, dvs = [], []
        for (cols, rows, keep), (st, dpt) in zip(strips, first):
            if keep is not None:
                st = _masked(st, keep, lead=True)
            pt = lax.exp(lax.sub(st, _under(_cut(lse, rows), st)))
            dvs.append(_dot(lax.convert_element_type(pt, do.dtype),
                            _cut(do, rows), (1, 0), prec))
            dst = lax.mul(pt, lax.sub(dpt, _under(_cut(delta, rows), dpt)))
            dks.append(_dot(lax.convert_element_type(dst, q.dtype),
                            _cut(q, rows), (1, 0), prec))
        done = strips[-1][0][1]       # the strips' k rows run from 0 on
        dk_sc[:done, :] += _stack(dks)
        dv_sc[:done, :] += _stack(dvs)

    def full(masked):
        add([((0, block_k), (0, block_q),
              _keep((block_k, block_q), q_off, k_off, transposed=True)
              if masked else None)])

    def walk():
        # k sub-block j against the q rows from its own on down: the
        # triangle leads the strip
        c = plan.sub
        tri = _keep((c, c), 0, 0, transposed=True)
        add([((j * c, j * c + c), (j * c, block_q), tri)
             for j in range(min(block_k, block_q) // c)])

    if plan is None:
        full(False)
    else:
        _causal_branches(plan, q_off, k_off, block_q, block_k, full, walk)

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
    plan = _causal_plan(tq, tk, block_q, block_k) if causal else None

    dq, delta = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, plan=plan, sm_scale=sm_scale),
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
                          block_k=block_k, plan=plan, sm_scale=sm_scale),
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
    block, the caller takes the XLA path.

    1024 was tuned on the chip at T = 2,048 and 8,192, where the grid skips
    the blocks above the diagonal. At T <= 1,024 a head is ONE grid block
    and the grid skips nothing: a causal call's skip happens inside the
    block there (_causal_plan), and smaller grid blocks are no substitute
    (docs/perf_notes.md round 4: 1.4x dearer per unit of work)."""
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
_dispatch = {"pallas": 0, "reference": 0,
             "causal_subblocks_run": 0, "causal_subblocks_all": 0}
_dispatch_lock = threading.Lock()


def dispatch_stats():
    """{"pallas": n, "reference": n}: traced flash_attention/flash_hop
    calls served by the Pallas kernels vs dropped to attention_reference;
    "causal_subblocks_run" of "causal_subblocks_all": over the traced
    CAUSAL forward kernel calls, the score sub-blocks one head computes
    and those in its (Tq, Tk) square (_causal_plan; the backward pair
    walks the same ones). All counted at trace time, nothing per step."""
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
    # v5e-tuned r4 at T=2048 and T=8192 only: (1024, 1024) — 33.8 TF/s
    # fwd at T=2048 (vs 30.5 at the r3 (512,1024) tune) and 53.4 at
    # T=8192 (vs 46.6); the r3 sweep predates the backward/block interplay
    # (docs/perf_notes.md). T <= 1024 is one block a head: _pick_block
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
        # v5e block sweep at T=2048 (docs/perf_notes.md round 4):
        # (1024,1024) runs the backward pair at 34.3 TF/s vs 28.9 at the
        # old (512,512); below T=2048 see _pick_block
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
