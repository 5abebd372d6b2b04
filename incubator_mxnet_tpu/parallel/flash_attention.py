"""Flash attention as a Pallas TPU kernel.

The hot op the reference implements as fused CUDA matmuls
(src/operator/contrib/transformer.cc interleaved-matmul attention) —
here a real blocked online-softmax kernel: one grid instance per
(batch, block of heads, q_block), K/V streamed block-by-block from VMEM
with running (max, sumexp, acc) statistics, so the full (Tq, Tk) score
matrix never materializes in HBM. O(T) memory instead of O(T^2), the
standard flash-attention recurrence (Dao et al.; same math as
ring_attention._block_attn).

Public entry `flash_attention(q, k, v, causal, sm_scale)` uses the
reference layout (B, T, H, D) and falls back to `attention_reference`
when the shape doesn't tile (tiny heads / ragged lengths). Off-TPU the
kernel runs in Pallas interpret mode, so the same code path is tested on
the CPU mesh.

The kernels index the caller's layout: over the free reshape (B, T, H*D)
a block of 128 lanes holds 128 // D whole heads (two of 64), which a
kernel takes one at a time, so no transpose or copy stands between the
projections' matmuls and a call. Shapes that do not pack so (an odd head
count, D = 80) are transposed to (B*H, T, D) and take the same kernels
with one head a block (_direct).

Causal calls skip the masked half twice: the grid skips the blocks above
the diagonal, and a block ON the diagonal is walked in strips of 256 rows
that stop at the diagonal, with the mask on each strip's one 256 x 256
triangle (_causal_plan). At T <= 1,024 a head is one grid block, so the
walk is all the skipping there is.

Backward: REAL flash backward kernels (custom_vjp) — the forward also
emits the per-row log-sum-exp; `_fa_bwd_dq_kernel` streams k/v blocks
accumulating dq, `_fa_bwd_dkv_kernel` streams q blocks accumulating
dk/dv, both recomputing p from the saved lse with bf16 matmuls and f32
accumulation. That pair computes every score block twice. A call whose dq
need not cross the grid's outer axis, or can be carried across it, is ONE
call of the dk/dv kernel instead, which gives dq as well from the one p and
ds a strip holds: five matrix products a head and one pass of exp where the
pair makes seven and two, and delta never leaves the chip. That is a call of
one grid block in q and in k (both lengths up to 1,024, no window), and
every causal self-attention call of several, plain or windowed, whose head's
dq (T, 128) f32 fits the chip's VMEM beside a step's buffers: the kernel
walks k blocks outside and q blocks inside, adds each step's part to its q
block's rows of the head's dq, and writes a q block's dq at its own k
block's diagonal step, after which no k block meets it (_fa_backward; the
call bears the dq kernel's name, which the benchmark leads a layer's
backward from). lse and delta pass between the kernels as (heads, 1, T)
f32, rows of lanes that no tile pads; the forward's output and lse carry
names (RESIDUAL_NAMES) under which a caller's checkpoint keeps them, so
that its backward runs no forward kernel again (TransformerLM's blocks
do). O(block * T) memory end to end, which is what makes
LONG-CONTEXT TRAINING possible on one chip, where the XLA attention path
cannot even compile at T = 8,192 (speeds: PERF.md). An XLA lax.scan
fallback covers untileable shapes and the no-pallas path.
"""
from __future__ import annotations

import collections
import functools
import logging
import threading

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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


# The kernels' bodies are written in lax, not jnp, down to the arithmetic on
# the grid's indices: a jnp function, an operator on a traced value among
# them, is a jit of its own, and tracing one costs five times what binding the
# primitive does.
# A train step traces and lowers two or three kernels a layer in every process,
# and a ref load is the dearest thing to lower, so each kernel loads its blocks
# once and cuts strips out of the values (PERF.md section 6, PR 26).

def _keep(shape, q_off, k_off, transposed=False):
    """Causal (q_row >= k_row) mask of a score block whose first row and
    column sit at q_off / k_off. shape is (q rows, k rows), or (k rows,
    q rows) when transposed."""
    from jax import lax
    a = lax.broadcasted_iota(jnp.int32, shape, 0)
    b = lax.broadcasted_iota(jnp.int32, shape, 1)
    q, k = (b, a) if transposed else (a, b)   # which of them counts q rows
    # a sub-block on the diagonal sits at the numbers (0, 0): no op to add
    at = lambda x, off: x if isinstance(off, int) and off == 0 \
        else lax.add(x, off)
    return lax.ge(at(q, q_off), at(k, k_off))


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


def _masked_at(s, pieces):
    """s with -inf where a piece's mask is False: `pieces` are (first column,
    last, keep) in order, each `keep` as wide as its columns; what lies
    between them stays as it is (a band's strip: _band_strips)."""
    from jax import lax
    out, at = [], 0
    for lo, hi, keep in pieces:
        if lo > at:
            out.append(_cut(s, cols=(at, lo)))
        on = _cut(s, cols=(lo, hi))
        out.append(lax.select(keep, on, lax.full_like(on, _NEG_INF)))
        at = hi
    if at < s.shape[1]:
        out.append(_cut(s, cols=(at, s.shape[1])))
    return _stack(out, axis=1)


def _apply(s, keep, lead=False):
    """s under a strip's `keep`: None, one mask for _masked, or a band's
    pieces for _masked_at."""
    if keep is None:
        return s
    return _masked_at(s, keep) if isinstance(keep, tuple) \
        else _masked(s, keep, lead)


def _over(col, like):
    """A column (r, 1) spread over the lanes of `like` (r, w)."""
    from jax import lax
    return lax.broadcast_in_dim(col, like.shape, (0, 1))


def _under(row, cols, like):
    """Lanes `cols` of a row (1, n) spread down the sublanes of `like` (r,
    w): spread first and cut then, since Mosaic spreads no row that starts
    off lane 0."""
    from jax import lax
    flat = lax.squeeze(row, (0,))
    if cols != (0, flat.shape[0]):
        flat = lax.slice_in_dim(flat, cols[0], cols[1], axis=0)
    return lax.broadcast_in_dim(flat, like.shape, (1,))


# The row vectors (lse, dlse, delta) lie in HBM as rows of lanes, a head a
# row: a (T, 1) f32 column is tiled T(8,128) there, 128 times its numbers
# (PERF.md section 6, PR 35). A kernel that wants a row's numbers down its
# score block's sublanes turns the row on the chip, once a head a grid step.

def _row(col):
    """A column (r, 1) as a row of lanes (1, r): a transpose, which Mosaic
    gives to the transpose unit; its own relayout of the squeezed column
    costs 1.3x the forward kernel's bundles (PERF.md section 6, PR 35)."""
    from jax import lax
    return lax.transpose(col, (1, 0))


def _col(row):
    """A row of lanes (1, r) as a column (r, 1)."""
    from jax import lax
    return lax.expand_dims(lax.squeeze(row, (0,)), (1,))


def _row_sums(x):
    """The sums of x's rows, (r, w), as a row of lanes (1, r)."""
    from jax import lax
    return _row(lax.expand_dims(lax.reduce_sum(x, (1,)), (1,)))


def _f32(x):
    from jax import lax
    return lax.convert_element_type(x, jnp.float32)


def _dot(a, b, contract, prec):
    """f32 a . b contracting a's dim contract[0] with b's contract[1].
    bf16 operands keep full MXU rate with f32 accumulation; precision
    comes from _prec (DEFAULT for bf16 — Mosaic requires it — HIGHEST for
    f32 inputs)."""
    from jax import lax
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                           precision=prec,
                           preferred_element_type=jnp.float32)


# A kernel block is (rows, w) over arrays (N, T, heads * d): w lanes hold
# w // d whole heads, side by side. The kernels take one head of the block at
# a time, in a ROLLED loop (one copy of the body whatever w // d is: a body's
# size is set-up, see _SUB), and tell the heads apart by lanes alone: an
# operand with the other heads' lanes zeroed, contracted over all w lanes,
# gives this head's exact product (the zeros add 0.0 in f32) in the time the
# d-deep one takes, and a product w lanes wide holds every head's columns, of
# which a select keeps this head's. No lane is sliced or shifted.

def _lanes(h, d, shape):
    """Mask, of `shape` (rows, w), of the lanes that head h of a block
    holds: d of w. None where the block is one head."""
    from jax import lax
    if shape[1] == d:
        return None
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    # d divides 128 here: a power of two
    return lax.eq(lax.shift_right_logical(lane, d.bit_length() - 1), h)


def _scaled(x, scale, h, d):
    """x (rows, w) times `scale`, rounded to x's dtype as the product of
    two such numbers is, with zeros in the lanes that are not head h's."""
    from jax import lax
    import numpy as np
    lanes = _lanes(h, d, (1, x.shape[1]))
    if lanes is None:
        return x if scale == 1.0 else lax.mul(x, np.asarray(scale, x.dtype))
    # the select in f32: Mosaic has no relayout of a (1, w) mask for bf16
    s = lax.full(lanes.shape, float(np.asarray(scale, x.dtype)), jnp.float32)
    s = lax.convert_element_type(
        lax.select(lanes, s, lax.full_like(s, 0)), x.dtype)
    return lax.mul(x, lax.broadcast_in_dim(s, x.shape, (0, 1)))


def _kept(val, h, d):
    """val (rows, w) with zeros in the lanes that are not head h's."""
    from jax import lax
    lanes = _lanes(h, d, val.shape)
    return val if lanes is None else \
        lax.select(lanes, val, lax.full_like(val, 0))


def _put(ref, at, val, h, d):
    """Write head h's lanes of val (rows, w) to ref[at]; the other lanes
    stay as they are."""
    from jax import lax
    lanes = _lanes(h, d, val.shape)
    if lanes is not None:
        val = lax.select(lanes, val,
                         lax.convert_element_type(ref[at], val.dtype))
    ref[at] = lax.convert_element_type(val, ref.dtype)


def _each_head(n, head):
    """head(h) for each of a block's n heads: a rolled loop over a traced
    h, so that the body is traced and lowered once; plain head(0) for a
    block of one head."""
    from jax import lax
    if n == 1:
        head(0)
    else:
        lax.fori_loop(0, n, lambda h, _: head(h), None)


# Edge of the score sub-blocks a grid block ON the diagonal is walked in, in
# all three kernels. On the chip 128 runs the three kernels 6%, 3% and 7%
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
    from jax import lax
    from jax.experimental import pallas as pl
    below = lax.le(lax.add(k_off, block_k - 1), q_off)
    diag = lax.eq(q_off, k_off)
    if plan.below:
        pl.when(below)(lambda: full(False))
    pl.when(diag)(walk if plan.sub else (lambda: full(True)))
    if plan.straddle:
        reach = lax.le(k_off, lax.add(q_off, block_q - 1))
        pl.when(lax.bitwise_and(reach, lax.bitwise_not(
            lax.bitwise_or(below, diag))))(lambda: full(True))


# A WINDOWED causal call (query i reads keys j with 0 <= i - j < window) runs,
# for a q block, only the k blocks that meet the band: its own and the
# `n_k - 1` before it, the grid's last axis (the index maps clamp at the
# sequence's edge, so that a block the band does not reach is neither
# fetched nor computed). Inside a block the sub-blocks the band misses are
# skipped as the causal walk skips those above the diagonal, and only the
# sub-blocks the band's two edges cross are masked. Lengths and blocks are
# equal on both sides (self-attention). `run` of `all`: score sub-blocks a
# head computes, and those in its (T, T) square, as _Plan counts them.
_Band = collections.namedtuple("_Band", "window sub n_k blocks run all")


def _band_strips(block, c, delta, window, by_k=False):
    """The strips of the grid block whose q rows start `delta` blocks past
    its k rows, in sub-blocks of edge c: ((outer rows), (inner rows),
    pieces), a strip a sub-block of q rows (of k rows with `by_k`: the
    dk/dv kernel's transposed scores) against the contiguous k (q)
    sub-blocks the band lets it meet. `pieces`: (first, last, offset,
    causal, windowed) of each inner sub-block that an edge of the band
    crosses, in the strip's own columns; offset = its q rows' start less
    its k rows'. A block the band reaches only in part leaves out whole
    strips: the q rows (k rows) left are a leading (trailing) run."""
    n = block // c
    reach = (window + c - 2) // c        # farthest sub-block the band meets
    out = []
    for a in range(n):
        if by_k:
            lo, hi = max(a - delta * n, 0), min(a - delta * n + reach, n - 1)
        else:
            lo, hi = max(delta * n + a - reach, 0), min(delta * n + a, n - 1)
        if lo > hi:
            continue
        pieces = []
        for b in range(lo, hi + 1):
            s = delta * n + (b - a if by_k else a - b)
            causal, windowed = s == 0, (s + 1) * c > window
            if causal or windowed:
                pieces.append(((b - lo) * c, (b - lo + 1) * c, s * c, causal,
                               windowed))
        out.append(((a * c, a * c + c), (lo * c, hi * c + c), tuple(pieces)))
    return tuple(out)


def _band_plan(t, block, window):
    """The _Band of a windowed causal call over (t, t) in grid blocks of
    `block` a side."""
    sub = _sub_block(block, block)
    c = sub or block
    n_k = min(1 + -(-(window - 1) // block), t // block)
    run = sum((cols[1] - cols[0]) // c
              for i in range(t // block) for delta in range(min(n_k, i + 1))
              for _, cols, _ in _band_strips(block, c, delta, window))
    return _Band(window, sub, n_k, t // block, run, (t // c) ** 2)


def _band_branches(band, block, at, step, run, by_k=False):
    """run(strips) with the strips of the grid block at outer block `at`,
    inner step `step` of band.n_k: step s of a q block reads the k block
    n_k - 1 - s before it (of a k block, with `by_k`, the q block s after
    it); a step past the sequence's edge runs nothing. `strips` carry their
    masks built (_masked_at's pieces); steps that share strips share a
    branch."""
    from jax import lax
    from jax.experimental import pallas as pl
    c = band.sub or block
    branches = {}

    def keep(masks, off, causal, windowed):
        """The mask of one sub-block an edge crosses; `masks` are its
        branch's own (a value traced in one branch is no other's)."""
        if (off, causal, windowed) not in masks:
            a = lax.broadcasted_iota(jnp.int32, (c, c), 0)
            b = lax.broadcasted_iota(jnp.int32, (c, c), 1)
            gap = lax.sub(a, b) if not by_k else lax.sub(b, a)  # q row - k
            m = lax.ge(gap, -off) if causal else None
            if windowed:
                w = lax.lt(gap, band.window - off)
                m = w if m is None else lax.bitwise_and(m, w)
            masks[off, causal, windowed] = m
        return masks[off, causal, windowed]

    for s in range(band.n_k):
        delta = s if by_k else band.n_k - 1 - s
        strips = _band_strips(block, c, delta, band.window, by_k)
        if strips:
            branches.setdefault(strips, []).append((s, delta))
    for strips, steps in branches.items():
        hit = None
        for s, delta in steps:
            ok = lax.eq(step, s)
            if delta:
                ok = lax.bitwise_and(ok, lax.le(lax.add(at, delta),
                                                band.blocks - 1)
                                     if by_k else lax.ge(at, delta))
            hit = ok if hit is None else lax.bitwise_or(hit, ok)

        def branch(strips=strips):
            masks = {}
            run([(outer, inner, tuple(
                (lo, hi, keep(masks, *what)) for lo, hi, *what in pieces)
                or None) for outer, inner, pieces in strips])
        pl.when(hit)(branch)


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


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, d, block_q,
               block_k, plan, sm_scale, band=None):
    """One (row, lane block, q_block, kv_block) grid step. The kv axis is
    the innermost ('arbitrary') grid dimension, so Pallas double-buffers
    the K/V block DMAs while this step computes; running (max, sumexp,
    acc) stats live in VMEM scratch that persists across kv steps. `plan`
    is None for a non-causal call (every block one unmasked pass), else
    the call's _causal_plan; a windowed call has a `band` too (_Band).
    Without scratch the grid step is its heads'
    only one and is walked in strips that each hold their rows' every
    score: the statistics go straight to the output.

    Refs, g = w // d heads a block: q (1, block_q, w) | k, v (1, block_k,
    w) | o (1, block_q, w) | lse (g, 1, block_q), a row of lanes a head;
    scratch m, l (g, block_q, 1), columns as the fold uses them, acc
    (block_q, w)."""
    from jax import lax
    from jax.experimental import pallas as pl

    j = pl.program_id(3)
    n_k = pl.num_programs(3)
    q_off = lax.mul(pl.program_id(2), block_q)
    k_off = lax.mul(j, block_k)
    prec = _prec(q_ref.dtype)
    heads = q_ref.shape[2] // d
    if scratch:
        m_sc, l_sc, acc_sc = scratch

        @pl.when(lax.eq(j, 0))
        def _init():
            m_sc[:] = lax.full(m_sc.shape, _NEG_INF, m_sc.dtype)
            l_sc[:] = lax.full(l_sc.shape, 0.0, l_sc.dtype)
            acc_sc[:] = lax.full(acc_sc.shape, 0.0, acc_sc.dtype)

    def emit(h, parts):
        """Head h's output and lse from `parts`: the final (m, l, acc) of
        its q rows, strip by strip. Each strip's lse is turned into a row
        of lanes by itself, under the strips that follow it."""
        outs, lses = [], []
        for m, l, acc in parts:
            none = lax.eq(l, 0.0)
            some = lax.select(none, lax.full_like(l, 1.0), l)
            # fully-masked rows: zeros out, and a +inf-ish log-sum-exp so
            # that exp(s - lse) underflows to 0 in the backward kernels
            outs.append(lax.div(acc, _over(some, acc)))
            lses.append(_row(lax.select(none, lax.full_like(l, 1e30),
                                        lax.add(m, lax.log(some)))))
        _put(o_ref, 0, _stack(outs), h, d)
        lse_ref[h] = _stack(lses, axis=1)

    def fold(strips):
        """Fold k rows `cols`, masked by `keep`, into the running stats
        of q rows `rows`, for each (rows, cols, keep) of `strips`, which
        together hold every q row once, head by head: every strip's scores
        first, so that no strip's first matmul queues behind another's
        second on its MXU."""
        def head(h):
            q = _scaled(q_ref[0], sm_scale, h, d)
            k, v = k_ref[0], v_ref[0]
            old = (m_sc[h], l_sc[h], acc_sc[:]) if scratch else None
            scores = [_dot(_cut(q, rows), _cut(k, cols), (1, 1), prec)
                      for rows, cols, _ in strips]
            new = [_fwd_fold(old and [_cut(x, rows) for x in old],
                             _apply(s, keep), _cut(v, cols), prec)
                   for (rows, cols, keep), s in zip(strips, scores)]
            if scratch:
                m, l, acc = (_stack(list(x)) for x in zip(*new))
                done = strips[-1][0][1]
                if done < block_q:      # a band's far block: leading rows
                    m, l = (_stack([x, _cut(o, (done, block_q))])
                            for x, o in zip((m, l), old))
                m_sc[h], l_sc[h] = m, l
                _put(acc_sc, slice(None) if done == block_q
                     else slice(0, done), acc, h, d)
            else:
                emit(h, new)
        _each_head(heads, head)

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
    elif band is not None:
        _band_branches(band, block_q, pl.program_id(2), j, fold)
    else:
        _causal_branches(plan, q_off, k_off, block_q, block_k, full, walk)

    if scratch:
        @pl.when(lax.eq(j, lax.sub(n_k, 1)))
        def _finish():
            _each_head(heads,
                       lambda h: emit(h, [(m_sc[h], l_sc[h], acc_sc[:])]))


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))


def _interpret():
    return jax.default_backend() != "tpu"


# What the kernels take: arrays (N, T, C) whose rows hold C // d heads of d
# lanes side by side, cut along C into blocks of _lane_block lanes; the row
# vectors (lse, dlse, delta) as (N * C // d, 1, T) f32, a head a row of
# lanes (_row_spec). A caller
# with (B, T, H, D) gets there by one of two routes, chosen from the shape
# alone (_direct): its FREE reshape (B, T, H * D), where a 128-lane block
# holds whole heads, so that no copy stands round a call; or the transpose
# (B * H, T, D), one head a row, the block spanning it (a pass over the array
# for each operand and each result).

def _lane_block(c, d):
    """Lanes of a block over rows of c = heads * d lanes: the row where it
    is one head, else the fewest whole tiles of 128 lanes that are whole
    heads."""
    w = d if c == d or d % 128 == 0 else 128
    assert w % d == 0 and c % w == 0, (c, d)
    return w


def _direct(heads, d):
    """Whether (B, T, heads, d) reaches the kernels as it lies: blocks of
    128 // d heads (a pair of 64-wide ones), or of one head whose d is a
    multiple of 128."""
    return d % 128 == 0 or (128 % d == 0 and heads % (128 // d) == 0)


def _operand(x, direct):
    """(B, T, H, D) as the kernels take it, by the route `direct` names."""
    B, T, H, D = x.shape
    return x.reshape(B, T, H * D) if direct \
        else x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _result(x, like, direct):
    """A kernel's (N, T, C) result in the layout of the caller's `like`."""
    B, T, H, D = like.shape
    return x.reshape(B, T, H, D) if direct \
        else x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _row_spec(g, n_p, block_q, q_axis, q_block=None):
    """Block of a row vector (N * C // d, 1, Tq): the g heads of lane block
    p of row b, q block `q_axis` (2 or 3) of the grid's indices, or
    q_block(the grid's last two indices) where a band picks it."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((g, 1, block_q), lambda *at: (
        at[0] * n_p + at[1], 0,
        at[q_axis] if q_block is None else q_block(*at[2:])))


def _band_of(window, causal, tq, tk, block_q, block_k):
    """The _Band of a call with a `window`, None without one."""
    if window is None:
        return None
    if not causal or tq != tk or block_q != block_k or window < 1:
        raise ValueError(f"a window of {window} wants causal self-attention "
                         f"in square blocks: causal {causal}, lengths "
                         f"({tq}, {tk}), blocks ({block_q}, {block_k})")
    return _band_plan(tq, block_q, window)


def _band_k_spec(block, w, n_k):
    """The k block a q block reads at a band's step: n_k - 1 - step before
    its own, held at the sequence's first (and not fetched again) where
    the band ends before it."""
    from jax import lax
    from jax.experimental import pallas as pl
    return pl.BlockSpec((1, block, w), lambda b, p, i, j: (
        b, lax.max(i - (n_k - 1) + j, 0), p))


def _fa_forward(q, k, v, d, causal, sm_scale, block_q, block_k, interpret,
                window=None):
    """q, k, v: (N, T, C), rows of C // d heads. Returns (out, lse) with lse
    the per-row log-sum-exp (N * C // d, 1, T) f32 the backward kernels
    consume. With a `window` the kernel is `flash_win_fwd`: the same body
    over the band's grid (_Band)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, tq, c = q.shape
    tk = k.shape[1]
    w = _lane_block(c, d)
    g, n_p = w // d, c // w
    band = _band_of(window, causal, tq, tk, block_q, block_k)
    grid = (n, n_p, tq // block_q, band.n_k if band else tk // block_k)
    plan = _causal_plan(tq, tk, block_q, block_k) if causal else None
    if causal:
        with _dispatch_lock:
            if band:
                _dispatch["window_subblocks_run"] += band.run
                _dispatch["window_subblocks_all"] += band.all
            else:
                _dispatch["causal_subblocks_run"] += plan.run
                _dispatch["causal_subblocks_all"] += plan.all
    kern = functools.partial(_fa_kernel, d=d, block_q=block_q,
                             block_k=block_k, plan=plan, sm_scale=sm_scale,
                             **({"band": band} if band else {}))
    # heads that are one grid block walked in strips carry no running
    # statistics from one kv step to the next: no scratch
    alone = causal and plan.sub and grid[2:] == (1, 1)
    of_q = pl.BlockSpec((1, block_q, w), lambda b, p, i, j: (b, i, p))
    of_k = _band_k_spec(block_k, w, band.n_k) if band else \
        pl.BlockSpec((1, block_k, w), lambda b, p, i, j: (b, j, p))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[of_q, of_k, of_k],
        out_specs=[of_q, _row_spec(g, n_p, block_q, 2)],
        out_shape=[jax.ShapeDtypeStruct((n, tq, c), q.dtype),
                   jax.ShapeDtypeStruct((n * c // d, 1, tq), jnp.float32)],
        scratch_shapes=[] if alone else [
            pltpu.VMEM((g, block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((g, block_q, 1), jnp.float32),  # running sumexp
            pltpu.VMEM((block_q, w), jnp.float32),     # output accumulator
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_win_fwd" if band else "flash_fwd",
    )(q, k, v)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, out_ref, *rest,
                      d, block_q, block_k, plan, sm_scale, band=None):
    """dq for one q block, streaming k/v blocks (innermost grid dim):
      delta = rowsum(dO * O) - dlse   (computed HERE at j==0 — fused, so
                                 no separate XLA pass re-reads dO and O;
                                 dlse is the cotangent of the emitted
                                 lse — d lse/d s = p, so it enters ds
                                 with the OPPOSITE sign of delta. Plain
                                 attention has none and passes no such
                                 operand; the ring-attention merge
                                 consumes lse and does.)
      p  = exp(s*scale - lse);  dp = dO V^T
      ds = p * (dp - delta);    dq = scale * sum_k ds K
    Matmuls keep input-dtype operands with f32 accumulation. delta is an
    output, for the dk/dv kernel to consume; its block stays where it is
    over the kv steps, which read it back. `plan` and the refs as in
    _fa_kernel; the row vectors are (g, 1, block_q), a row of lanes a head,
    turned into columns once a head a step. `rest`: dlse where the caller
    has one, then the results dq, delta and the scratch acc (block_q, w)."""
    from jax import lax
    from jax.experimental import pallas as pl

    j = pl.program_id(3)
    n_k = pl.num_programs(3)
    q_off = lax.mul(pl.program_id(2), block_q)
    k_off = lax.mul(j, block_k)
    prec = _prec(q_ref.dtype)
    heads = q_ref.shape[2] // d
    *dlse_ref, dq_ref, delta_ref, acc_sc = rest

    @pl.when(lax.eq(j, 0))
    def _init():
        acc_sc[:] = lax.full(acc_sc.shape, 0.0, acc_sc.dtype)

        def head(h):
            delta = _row_sums(_kept(
                lax.mul(_f32(do_ref[0]), _f32(out_ref[0])), h, d))
            delta_ref[h] = lax.sub(delta, dlse_ref[0][h]) if dlse_ref \
                else delta
        _each_head(heads, head)

    def add(strips):
        """Add to the dq of q rows `rows` what k rows `cols`, masked by
        `keep`, give, for each (rows, cols, keep) of `strips`, which
        together hold every q row once, head by head: every strip's two
        score matmuls first, so that none queues behind another strip's
        last."""
        def head(h):
            # scale q in the INPUT dtype before the dot, exactly like the
            # forward — a post-dot f32 scale would recompute a subtly
            # different s than the one that produced the saved lse
            qs = _scaled(q_ref[0], sm_scale, h, d)
            do = _scaled(do_ref[0], 1.0, h, d)
            k, v = k_ref[0], v_ref[0]
            lse, delta = _col(lse_ref[h]), _col(delta_ref[h])
            first = [(_dot(_cut(qs, rows), _cut(k, cols), (1, 1), prec),
                      _dot(_cut(do, rows), _cut(v, cols), (1, 1), prec))
                     for rows, cols, _ in strips]
            parts = []
            for (rows, cols, keep), (s, dp) in zip(strips, first):
                s = _apply(s, keep)
                ds = lax.mul(lax.exp(lax.sub(s, _over(_cut(lse, rows), s))),
                             lax.sub(dp, _over(_cut(delta, rows), dp)))
                parts.append(_dot(lax.convert_element_type(ds, k.dtype),
                                  _cut(k, cols), (1, 0), prec))
            done = strips[-1][0][1]     # short of block_q: a band's far block
            at = slice(None) if done == block_q else slice(0, done)
            acc_sc[at] = lax.add(acc_sc[at], _kept(_stack(parts), h, d))
        _each_head(heads, head)

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
    elif band is not None:
        _band_branches(band, block_q, pl.program_id(2), j, add)
    else:
        _causal_branches(plan, q_off, k_off, block_q, block_k, full, walk)

    @pl.when(lax.eq(j, lax.sub(n_k, 1)))
    def _finish():
        dq_ref[0] = lax.convert_element_type(lax.mul(acc_sc[:], sm_scale),
                                             dq_ref.dtype)


def _join_rows(acc, rows, part):
    """acc + part, as (first row, last, value): acc, None or such a triple,
    is the sum so far; `part` holds `rows`, which start and end no earlier
    than acc's and leave no gap after them (a walk's strips go down the
    diagonal)."""
    from jax import lax
    if acc is None:
        return (*rows, part)
    lo, hi, val = acc
    a, b = rows
    assert lo <= a <= hi <= b, (acc[:2], rows)
    pieces = []
    if a > lo:
        pieces.append(_cut(val, (0, a - lo)))
    if hi > a:
        pieces.append(lax.add(_cut(val, (a - lo, hi - lo)),
                              _cut(part, (0, hi - a))))
    if b > hi:
        pieces.append(_cut(part, (hi - a, b - a)))
    return lo, b, _stack(pieces)


def _fa_bwd_dkv_kernel(*refs, d, block_q, block_k, plan, sm_scale, band=None,
                       alone=False, carried=False):
    """dk/dv for one k block, streaming q blocks (innermost grid dim):
      p^T  = exp(s^T*scale - lse);     dv = sum_q p^T dO
      ds^T = p^T * (dp^T - delta);     dk = scale * sum_q ds^T Q
    `plan` and the refs as in _fa_kernel; the walk goes by k sub-block
    here, over the q sub-blocks at and below the diagonal. The scores are
    transposed, so lse and delta are read as the rows of lanes they are.
    Refs: k, v, q, dO, lse, delta | dk, dv | scratch dk, dv (block_k, w).

    `alone` or `carried`, this kernel is the WHOLE backward: a strip's p^T
    and ds^T are all that dq needs as well, dq = scale * sum_k ds K, and
    delta = rowsum(dO * O) - dlse is computed here, a head at a time, for no
    other kernel to read. The call then bears the dq kernel's name and
    operand order (the benchmark's contract, _fa_backward). Refs: q, k, v,
    dO, lse, O[, dlse] | dq, dk, dv | scratch dk, dv, and dq. dq's product
    contracts over the LEADING axis of a strip, ds^T's k rows: Mosaic turns
    the strip for it, which by its schedule costs less than turning k once a
    head and dq once a block round a product with ds^T on its right
    (PERF.md section 6, PR 37).
    `alone`: the call is ONE grid block in q and in k, and the dq scratch is
    that block's (block_q, w).
    `carried`: causal self-attention over several grid blocks, plain or a
    band. dq of a q block is summed over the k blocks that meet it, which
    are this kernel's OUTER axis, so the scratch holds the whole head's dq
    (Tq, w) across them, each grid step adding to its q block's rows; a q
    block's first k block zeroes them, and its last is its OWN (no later k
    block meets a causal q block), at whose first step, the diagonal's, they
    are final: dq's output block goes by the outer axis and is written
    there, once."""
    from jax import lax
    from jax.experimental import pallas as pl

    whole = alone or carried
    if whole:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, out_ref, *dlse_ref,
         dq_ref, dk_ref, dv_ref, dk_sc, dv_sc, dq_sc) = refs
    else:
        (k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_sc, dv_sc) = refs
    i = pl.program_id(3)
    n_q = pl.num_programs(3)
    k_off = lax.mul(pl.program_id(2), block_k)
    q_off = lax.mul(i, block_q)
    prec = _prec(q_ref.dtype)
    heads = q_ref.shape[2] // d

    @pl.when(lax.eq(i, 0))
    def _init():
        dk_sc[:] = lax.full(dk_sc.shape, 0.0, dk_sc.dtype)
        dv_sc[:] = lax.full(dv_sc.shape, 0.0, dv_sc.dtype)
        if alone:
            dq_sc[:] = lax.full(dq_sc.shape, 0.0, dq_sc.dtype)

    if carried:
        # the q block this step meets (a band's step i: the i-th after the
        # k block's own) and its rows' place in the head's dq
        j = pl.program_id(2)
        q_at = lax.add(j, i) if band else i
        base = pl.multiple_of(lax.mul(q_at, block_q), block_q)
        begins = lax.eq(j, 0)
        if band:    # and no step past the sequence's edge: its q block is
            begins = lax.bitwise_and(     # the last one's, held (q_block)
                lax.bitwise_or(begins, lax.eq(i, band.n_k - 1)),
                lax.le(q_at, band.blocks - 1))

        @pl.when(begins)
        def _begin():
            dq_sc[pl.ds(base, block_q), :] = lax.full(
                (block_q, dq_sc.shape[1]), 0.0, dq_sc.dtype)

    def add(strips):
        """Add to the dk, dv of k rows `cols` what q rows `rows`, masked by
        `keep`, give, for each (cols, rows, keep) of `strips`, in
        transposed scores (k rows, q rows), head by head; the score matmuls
        first, as in the dq kernel. Strips that leave k rows out leave
        their dk, dv as they are. With dq to give, each strip adds its k
        rows' part to the dq of its q rows."""
        def head(h):
            q, k, v = q_ref[0], k_ref[0], v_ref[0]
            qs = _scaled(q, sm_scale, h, d)             # as the forward
            do = _scaled(do_ref[0], 1.0, h, d)
            lse = lse_ref[h]
            if whole:
                # dO has this head's lanes alone, and so has the product
                delta = _row_sums(lax.mul(_f32(do), _f32(out_ref[0])))
                if dlse_ref:
                    delta = lax.sub(delta, dlse_ref[0][h])
                kh = _scaled(k, 1.0, h, d)
            else:
                delta = delta_ref[h]
            first = [(_dot(_cut(k, cols), _cut(qs, rows), (1, 1), prec),
                      _dot(_cut(v, cols), _cut(do, rows), (1, 1), prec))
                     for cols, rows, _ in strips]
            dks, dvs, dq = [], [], None
            for (cols, rows, keep), (st, dpt) in zip(strips, first):
                st = _apply(st, keep, lead=True)
                pt = lax.exp(lax.sub(st, _under(lse, rows, st)))
                # dO has this head's lanes alone, so pt . dO leaves the
                # other heads' dv as it is; q has them all
                dvs.append(_dot(lax.convert_element_type(pt, do.dtype),
                                _cut(do, rows), (1, 0), prec))
                dst = lax.convert_element_type(
                    lax.mul(pt, lax.sub(dpt, _under(delta, rows, dpt))),
                    q.dtype)
                dks.append(_dot(dst, _cut(q, rows), (1, 0), prec))
                if whole:
                    # over the strip's LEADING axis, its k rows; kh has this
                    # head's lanes alone: the others' dq stays as it is
                    dq = _join_rows(dq, rows, _dot(dst, _cut(kh, cols),
                                                   (0, 0), prec))
            # the strips' k rows run from 0 on (a band's far block: up to
            # the block's end)
            at = slice(strips[0][0][0], strips[-1][0][1])
            dk_sc[at, :] = lax.add(dk_sc[at, :], _kept(_stack(dks), h, d))
            dv_sc[at, :] = lax.add(dv_sc[at, :], _stack(dvs))
            if alone:       # the strips' q rows are the block's
                dq_sc[:] = lax.add(dq_sc[:], dq[2])
            elif carried:   # a band's far block: a leading run of them
                at = pl.ds(lax.add(base, dq[0]) if dq[0] else base,
                           dq[1] - dq[0])
                dq_sc[at, :] = lax.add(dq_sc[at, :], dq[2])
        _each_head(heads, head)

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
    elif band is not None:
        _band_branches(band, block_k, pl.program_id(2), i, add, by_k=True)
    else:
        _causal_branches(plan, q_off, k_off, block_q, block_k, full, walk)

    if carried:
        @pl.when(lax.eq(q_at, j))
        def _dq_done():
            dq_ref[0] = lax.convert_element_type(
                lax.mul(dq_sc[pl.ds(base, block_q), :], sm_scale),
                dq_ref.dtype)

    @pl.when(lax.eq(i, lax.sub(n_q, 1)))
    def _finish():
        dk_ref[0] = lax.convert_element_type(lax.mul(dk_sc[:], sm_scale),
                                             dk_ref.dtype)
        dv_ref[0] = lax.convert_element_type(dv_sc[:], dv_ref.dtype)
        if alone:
            dq_ref[0] = lax.convert_element_type(
                lax.mul(dq_sc[:], sm_scale), dq_ref.dtype)


# Mosaic's own limit on a kernel's VMEM where a call names none (v5e).
_SCOPED_VMEM = 16 * 2**20


def _vmem_bytes():
    """A TensorCore's VMEM on the chip the process runs on; off a TPU
    (interpret mode, a compile for a described chip) a v5e's 128 MiB, the
    chip the blocks were tuned on."""
    try:
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except Exception:
        return 128 * 2**20


def _one_call_vmem(tq, block, w, g, itemsize, n_rows):
    """Bytes of VMEM the one backward call holds at a grid step, `carried`:
    what Pallas buffers twice (the blocks of q, k, v, dO, O in and dq, dk, dv
    out, the `n_rows` row vectors a head), the three f32 scratches (dk and dv
    of a block, dq of the whole head: tq rows) and two score blocks in f32
    (five with f32 operands), which bounds what Mosaic keeps of a step's
    strips (s^T turning into p^T, dp^T into ds^T; by its own count 1.77
    blocks at 1,024 rows, 4.85 for f32, less below: PERF.md section 6, PR
    39); lanes padded to a tile."""
    lanes = -(-w // 128) * 128
    piped = 2 * (8 * block * lanes * itemsize + n_rows * g * 8 * block * 4)
    scratch = (2 * block + tq) * lanes * 4
    return piped + scratch + (2 if itemsize <= 2 else 5) * block * block * 4


def _carried_vmem(q, d, block, dlse):
    """_one_call_vmem of the call over q (N, T, C), rows of C // d heads, in
    blocks of `block` rows."""
    w = _lane_block(q.shape[2], d)
    return _one_call_vmem(q.shape[1], block, w, w // d, q.dtype.itemsize,
                          1 if dlse is None else 2)


def _fa_backward(q, k, v, do, lse, out, dlse, d, causal, sm_scale, block_q,
                 block_k, interpret, window=None):
    """q, k, v, do, out: (N, T, C), rows of C // d heads; lse, and dlse
    where the caller has a cotangent for lse (None: the kernels take no
    such operand): (N * C // d, 1, Tq) f32. Returns (dq, dk, dv) via the
    flash backward kernels — O(block * T) memory, scores recomputed from
    the saved lse. Which, from what the call can see (causal or not, the two
    lengths against the blocks, the window, the lane block):

    ONE call, `flash_bwd_dq` by name (`flash_win_bwd_dq` with a `window`),
    of the dk/dv kernel, which gives dq too and computes delta for itself
    (_fa_backward_one), where dq need not be summed across the grid's outer
    axis: one grid block in q and in k; or where it can be carried across
    it: causal self-attention in square blocks, plain or a band, with the
    head's dq (Tq, w) f32 and the step's buffers inside half the chip's
    VMEM (_one_call_vmem);
    else the pair (_fa_backward_pair): a non-causal call of several blocks
    (dq of a q block is final only at the LAST k block), unequal lengths, a
    sequence too long for the scratch (a 128k ring shard)."""
    tq, tk = q.shape[1], k.shape[1]
    alone = window is None and (tq, tk) == (block_q, block_k)
    carried = not alone and causal and (tq, block_q) == (tk, block_k) \
        and _carried_vmem(q, d, block_q, dlse) <= _vmem_bytes() // 2
    with _dispatch_lock:
        _dispatch["bwd_fused" if alone or carried else "bwd_pair"] += 1
    return (_fa_backward_one if alone or carried else _fa_backward_pair)(
        q, k, v, do, lse, out, dlse, d, causal, sm_scale, block_q, block_k,
        interpret, window)


def _bwd_call(q, k, dlse, d, causal, sm_scale, block_q, block_k, window):
    """What the backward calls share: the lane block w, heads a block g,
    lane blocks a row n_p, the kernels' keyword arguments, the band, and
    the dq kernel's operand specs (the one call's too) with a q block's."""
    from jax.experimental import pallas as pl

    n, tq, c = q.shape
    tk = k.shape[1]
    w = _lane_block(c, d)
    g, n_p = w // d, c // w
    plan = _causal_plan(tq, tk, block_q, block_k) if causal else None
    band = _band_of(window, causal, tq, tk, block_q, block_k)
    sizes = dict(d=d, block_q=block_q, block_k=block_k, plan=plan,
                 sm_scale=sm_scale, **({"band": band} if band else {}))
    of_q = pl.BlockSpec((1, block_q, w), lambda b, p, i, j: (b, i, p))
    of_k = _band_k_spec(block_k, w, band.n_k) if band else \
        pl.BlockSpec((1, block_k, w), lambda b, p, i, j: (b, j, p))
    of_row = _row_spec(g, n_p, block_q, 2)
    specs = [of_q, of_k, of_k, of_q, of_row, of_q] \
        + [of_row] * (dlse is not None)
    return w, g, n_p, sizes, band, specs, of_q, of_k


def _fa_backward_one(q, k, v, do, lse, out, dlse, d, causal, sm_scale,
                     block_q, block_k, interpret, window=None):
    """_fa_backward's one call: the dk/dv kernel, giving dq as a third
    result. It is NAMED for the dq kernel and takes its operands in its
    order: the benchmark leads a layer's backward from that name and reads
    q, k, v off the first three operands (PERF.md section 3)."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, tq, c = q.shape
    tk = k.shape[1]
    w, g, n_p, sizes, band, specs, of_q, of_k = _bwd_call(
        q, k, dlse, d, causal, sm_scale, block_q, block_k, window)
    operands = (q, k, v, do, lse, out, *([] if dlse is None else [dlse]))
    out_shape = [jax.ShapeDtypeStruct((n, tq, c), q.dtype),
                 jax.ShapeDtypeStruct((n, tk, c), k.dtype),
                 jax.ShapeDtypeStruct((n, tk, c), v.dtype)]
    scratch = [pltpu.VMEM((block_k, w), jnp.float32),
               pltpu.VMEM((block_k, w), jnp.float32)]
    if band is None and (tq, tk) == (block_q, block_k):
        # one grid block in q and in k: nothing is accumulated across the
        # grid, and the dk/dv kernel's strips give dq as well
        return pl.pallas_call(
            functools.partial(_fa_bwd_dkv_kernel, alone=True, **sizes),
            grid=(n, n_p, 1, 1),
            in_specs=specs,
            out_specs=[of_q, of_k, of_k],
            out_shape=out_shape,
            scratch_shapes=scratch + [pltpu.VMEM((block_q, w), jnp.float32)],
            compiler_params=_compiler_params(),
            interpret=interpret,
            name="flash_bwd_dq",
        )(*operands)
    # k blocks outside, q blocks inside, as the dk/dv kernel walks them: a
    # band's k block meets its own q block and the n_k - 1 after it (held at
    # the sequence's last where the band ends before it), a plain one every
    # q block from its own on (held at its own above the diagonal, where
    # nothing runs: no block is fetched for it). dq's block goes by the k
    # block: it is written at the diagonal, the outer step's first
    q_block = (lambda j, i: lax.min(j + i, band.blocks - 1)) if band \
        else (lambda j, i: lax.max(i, j))
    of_q = pl.BlockSpec((1, block_q, w),
                        lambda b, p, j, i: (b, q_block(j, i), p))
    of_k = pl.BlockSpec((1, block_k, w), lambda b, p, j, i: (b, j, p))
    of_row = _row_spec(g, n_p, block_q, 3, q_block)
    return pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, carried=True, **sizes),
        grid=(n, n_p, tk // block_k, band.n_k if band else tq // block_q),
        in_specs=[of_q, of_k, of_k, of_q, of_row, of_q]
        + [of_row] * (dlse is not None),
        out_specs=[of_k, of_k, of_k],
        out_shape=out_shape,
        scratch_shapes=scratch + [pltpu.VMEM((tq, w), jnp.float32)],
        # the k blocks carry dq from one to the next: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=max(_SCOPED_VMEM,
                                 _carried_vmem(q, d, block_q, dlse))),
        interpret=interpret,
        name="flash_win_bwd_dq" if band else "flash_bwd_dq",
    )(*operands)


def _fa_backward_pair(q, k, v, do, lse, out, dlse, d, causal, sm_scale,
                      block_q, block_k, interpret, window=None):
    """_fa_backward's pair of calls, at any shape the kernels take:
    `flash_bwd_dq` streams k/v blocks for dq, and computes delta =
    rowsum(dO*O) - dlse INSIDE (per q block, at its first kv step), handing
    it to `flash_bwd_dkv` as an output shaped like lse — one fewer full
    pass over dO and O than a separate XLA delta computation. With a
    `window` the kernels are `flash_win_bwd_dq` and `flash_win_bwd_dkv`,
    over the band's grids. Every score block is computed twice: seven
    matrix products a head and two passes of exp."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, tq, c = q.shape
    tk = k.shape[1]
    w, g, n_p, sizes, band, specs, of_q, of_k = _bwd_call(
        q, k, dlse, d, causal, sm_scale, block_q, block_k, window)
    params = _compiler_params()
    row = jax.ShapeDtypeStruct((n * c // d, 1, tq), jnp.float32)
    of_row = _row_spec(g, n_p, block_q, 2)
    dq, delta = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, **sizes),
        grid=(n, n_p, tq // block_q, band.n_k if band else tk // block_k),
        in_specs=specs,
        out_specs=[of_q, of_row],
        out_shape=[jax.ShapeDtypeStruct((n, tq, c), q.dtype), row],
        scratch_shapes=[pltpu.VMEM((block_q, w), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_win_bwd_dq" if band else "flash_bwd_dq",
    )(q, k, v, do, lse, out, *([] if dlse is None else [dlse]))

    # the grid's last two axes swap: k blocks outside, q blocks inside (a
    # band's: the k block's own q block and the n_k - 1 after it, held at
    # the sequence's last where the band ends before it)
    q_block = (lambda j, i: lax.min(j + i, band.blocks - 1)) if band \
        else (lambda j, i: i)
    of_q = pl.BlockSpec((1, block_q, w),
                        lambda b, p, j, i: (b, q_block(j, i), p))
    of_k = pl.BlockSpec((1, block_k, w), lambda b, p, j, i: (b, j, p))
    of_row = _row_spec(g, n_p, block_q, 3, q_block if band else None)
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, **sizes),
        grid=(n, n_p, tk // block_k, band.n_k if band else tq // block_q),
        in_specs=[of_k, of_k, of_q, of_q, of_row, of_row],
        out_specs=[of_k, of_k],
        out_shape=[jax.ShapeDtypeStruct((n, tk, c), k.dtype),
                   jax.ShapeDtypeStruct((n, tk, c), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, w), jnp.float32),
                        pltpu.VMEM((block_k, w), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_win_bwd_dkv" if band else "flash_bwd_dkv",
    )(k, v, q, do, lse, delta)
    return dq, dk, dv


def _pick_block(t, preferred=1024):
    """Kernel block for an axis of length `t`, under the TPU tiling rule:
    the last two dims of every block are multiples of (8, 128) or span
    the array. A block's rows are q's or k's, and the lanes of the score
    block they make, so a partial block is a multiple of 128; an axis that
    fits in `preferred` is taken whole (rows in multiples of 8). None = no
    legal block, the caller takes the XLA path.

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
_dispatch = {"pallas": 0, "reference": 0, "direct": 0, "transposed": 0,
             "bwd_fused": 0, "bwd_pair": 0,
             "causal_subblocks_run": 0, "causal_subblocks_all": 0,
             "window_subblocks_run": 0, "window_subblocks_all": 0}
_dispatch_lock = threading.Lock()


def dispatch_stats():
    """{"pallas": n, "reference": n}: traced flash_attention/flash_hop
    calls served by the Pallas kernels vs dropped to attention_reference;
    of the former, "direct": those whose operands reached the kernels in
    the caller's layout, and "transposed": those whose operands were
    transposed to (B*H, T, D) first (_direct); "bwd_fused" and
    "bwd_pair": traced backward calls that were ONE kernel call (one grid
    block in q and in k, or causal self-attention whose dq fits the chip's
    VMEM) and those that were the dq / dkv pair (_fa_backward);
    "causal_subblocks_run" of "causal_subblocks_all": over the traced
    CAUSAL forward kernel calls, the score sub-blocks one head computes
    and those in its (Tq, Tk) square (_causal_plan; the backward pair
    walks the same ones); "window_subblocks_run" of "window_subblocks_all":
    the same over the traced WINDOWED calls (_Band), which the causal pair
    leaves out. All counted at trace time, nothing per step."""
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


def _route(heads, d):
    """_direct(heads, d), counted: once for each traced forward call."""
    direct = _direct(heads, d)
    with _dispatch_lock:
        _dispatch["direct" if direct else "transposed"] += 1
    return direct


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, window):
    return _flash_vjp_fwd(q, k, v, causal, sm_scale, window)[0]


def _flash_fwd_impl(q, k, v, causal, sm_scale, window=None):
    """(out, lse) as the forward kernel leaves them, out (N, T, C) by the
    route _direct names; (out, None) from attention_reference, out in q's
    layout, where no block fits."""
    from .ring_attention import attention_reference

    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    # v5e-tuned r4 at T=2048 and T=8192 only: (1024, 1024) — 33.8 TF/s
    # fwd at T=2048 (vs 30.5 at the r3 (512,1024) tune) and 53.4 at
    # T=8192 (vs 46.6); the r3 sweep predates the backward/block interplay
    # (docs/perf_notes.md). T <= 1024 is one block a head: _pick_block
    blocks = _blocks_for(Tq, Tk, D)
    if blocks is None:
        return attention_reference(q, k, v, causal=causal,
                                   sm_scale=sm_scale, window=window), None
    bq, bk = blocks
    direct = _route(H, D)
    return _fa_forward(_operand(q, direct), _operand(k, direct),
                       _operand(v, direct), D, causal, sm_scale, bq, bk,
                       _interpret(), window)


# What the Pallas forward leaves for its backward beside q, k, v, by the names
# a caller's `jax.checkpoint` policy can keep them under: with both kept, the
# recomputed forward is dead code (TransformerLM's blocks keep them: a layer's
# 66 MB at GPT-2 medium's sizes for a quarter of its recomputed time). The
# output is kept as the kernel wrote it, (N, T, C): the caller's (B, T, H, D)
# is another array to the chip's tiles wherever D is under 128 lanes, and XLA
# copied a residual of that shape twice a layer on its way to the backward
# kernels (PERF.md section 6, PR 35).
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _flash_vjp_fwd(q, k, v, causal, sm_scale, window):
    out, lse = _flash_fwd_impl(q, k, v, causal, sm_scale, window)
    if lse is None:
        # the scan fallback recomputes everything from q/k/v — keeping `out`
        # alive would cost an activation-sized residual for nothing
        return out, (q, k, v, None, None)
    out, lse = map(checkpoint_name, (out, lse), RESIDUAL_NAMES)
    return _result(out, q, _direct(*q.shape[2:])), (q, k, v, out, lse)


def _flash_vjp_bwd(causal, sm_scale, window, res, g):
    """Backward. With a Pallas forward (saved lse) the flash backward
    KERNELS run (one call of the dk/dv kernel that gives dq too, for a
    causal call; dq streaming k/v blocks and dk/dv streaming q blocks where
    it does not apply: _fa_backward) — O(block * T) memory (and a head's dq
    in VMEM), bf16 matmuls, f32 accumulation. Fallback (no pallas /
    untileable): an XLA lax.scan over q blocks with the same recompute
    math."""
    from jax import lax
    q, k, v, out, lse = res
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if lse is not None:
        # v5e block sweep at T=2048 (docs/perf_notes.md round 4):
        # (1024,1024) ran the backward pair at 34.3 TF/s vs 28.9 at the
        # old (512,512); below T=2048 see _pick_block
        bq = _pick_block(Tq)
        bk = _pick_block(Tk)
        direct = _direct(H, D)
        dq, dk, dv = _fa_backward(
            *(_operand(x, direct) for x in (q, k, v, g)), lse, out, None, D,
            causal, sm_scale, bq, bk, _interpret(), window)
        return (_result(dq, q, direct), _result(dk, k, direct),
                _result(dv, v, direct))
    bq = _pick_block(Tq, 256)
    if bq is None or bq == Tq or window is not None:
        # tiny/ragged: dense vjp of the reference is fine at this size
        from .ring_attention import attention_reference
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(
                q_, k_, v_, causal=causal, sm_scale=sm_scale, window=window),
            q, k, v)
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


def flash_attention(q, k, v, causal=False, sm_scale=None, window=None):
    """Blocked flash attention. q,k,v: (B, T, H, D) (the layout of
    attention_reference / the transformer flagship). Differentiable.
    `window`: query i reads keys j with 0 <= i - j < window (causal
    self-attention only); the kernels then run the band alone and are named
    `flash_win_fwd`, `flash_win_bwd_dq` (and `flash_win_bwd_dkv` where the
    backward is the pair), since the benchmark counts a `flash_fwd` as a
    whole causal square.
    Grouped K/V heads: k and v may hold H / g heads, query head i reading
    head i // g; they are repeated over the group before the kernels, whose
    operands are then three arrays of q's shape (what the benchmark's count
    of a call's work reads, perfbench/op_scopes.flash_dims), and the
    repeat's own transpose sums dk and dv over the group."""
    if sm_scale is None:
        import math
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if k.shape[2] != q.shape[2]:
        k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)
                for x in (k, v))
    if window is not None and (not causal or q.shape[1] != k.shape[1]):
        raise ValueError(f"window {window}: causal self-attention only")
    return _flash(q, k, v, bool(causal), float(sm_scale),
                  None if window is None else int(window))


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
    direct = _route(H, D)
    out, lse = _fa_forward(_operand(q, direct), _operand(k, direct),
                           _operand(v, direct), D, causal, sm_scale, bq, bk,
                           _interpret())
    lse_bht = lse.reshape(B, H, T)
    lse_bht = jnp.where(lse_bht >= 1e29, -jnp.inf, lse_bht)
    return (_result(out, q, direct).astype(jnp.float32), lse_bht)


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
        B * H, 1, Tq).astype(jnp.float32)
    dlse = g_lse.reshape(B * H, 1, Tq).astype(jnp.float32)
    direct = _direct(H, D)
    dq, dk, dv = _fa_backward(
        *(_operand(x, direct) for x in (q, k, v, g_out.astype(q.dtype))),
        lse_kern, _operand(out.astype(q.dtype), direct), dlse, D, causal,
        sm_scale, bq, bk, _interpret())
    return (_result(dq, q, direct).astype(q.dtype),
            _result(dk, k, direct).astype(k.dtype),
            _result(dv, v, direct).astype(v.dtype))


flash_hop.defvjp(_flash_hop_vjp_fwd, _flash_hop_vjp_bwd)


def flash_attention_bh(q, k, v, causal=False, sm_scale=None):
    """(BH, T, D)-layout flash attention for callers that already hold
    merged batch*head arrays: a singleton-head view of flash_attention
    (the (BH,T,1,D) reshape is free, and so is its transpose), so
    it shares the kernels, the custom vjp, AND the O(block*T) scan
    fallback. Routing the transformer through this entry, its
    projections emitting (BH,T,hd), was measured 4.4% SLOWER end to end
    than transposing round the kernels (docs/perf_notes.md round-4
    addendum): the copies moved into XLA's einsums. Since PR 33 the
    model's own (B,T,H,D) reaches the kernels with no copy at all
    (_direct); this entry is for code that genuinely starts from
    (BH,T,D)."""
    return flash_attention(q[:, :, None, :], k[:, :, None, :],
                           v[:, :, None, :], causal=causal,
                           sm_scale=sm_scale)[:, :, 0, :]
