"""Fused dgrad+wgrad Pallas kernel for 3x3 stride-1 'same' convolutions.

The ResNet train step is HBM-roofline-bound in XLA's conv backward: the
two backward ops (grad-input and grad-weight) each re-read grad_out and
XLA materializes transposed/sliced copies on top, ~2x the fundamental
traffic (docs/perf_notes.md "Why train MFU saturates"). The reference
answered the same problem on GPU with hand kernels
(src/operator/nn/depthwise_convolution_tf.cuh, im2col.cuh); the TPU
answer is this fused kernel: ONE pass over grad_out and x computes BOTH
gradients —

  per batch-block (sequential grid), with x and grad_out zero-padded
  into VMEM scratch once:
    for each of the 9 taps (kh, kw):
      dW[kh,kw] += x_shift(kh,kw)^T . grad_out           (I,O)
      dx        += grad_out_shift(2-kh,2-kw) . W[kh,kw]^T (M,I)

HBM traffic = read x + read grad_out + write dx (+ tiny dW), the
fundamental minimum; all shifting happens on the VMEM-resident padded
copies. Two formulations are implemented: `_patch_kernel` (im2col in
VMEM, two K=9C / K=M matmuls) and `_bwd_kernel` (9 taps, 18 K=C
matmuls), selectable via MXTPU_CONV_BWD_KERNEL=patch|taps.

MEASURED RESULT (v5e, round 4 — docs/perf_notes.md "Fused conv-backward
Pallas kernel"): the kernel LOSES to XLA's native conv backward at every
ResNet-50 shape (best kernel 439-1,733us vs XLA fwd+bwd 312-934us per
128-image conv). XLA's v5e conv emitter is already at 98-150 TF/s
op-level — the round-3 "2x traffic" hypothesis was an artifact of
in-step self-time attribution, not op-level waste. The kernel therefore
stays OPT-IN (MXTPU_FUSED_CONV_BWD=1) as the measured-negative record
and a base for future shapes XLA handles badly; exactness vs the XLA
vjp is kept gated in tests/test_conv_backward.py.

Layout: NHWC inside (channel-minor = MXU lane dim). The public
`conv3x3_bwd_fused(x, w, go)` takes the framework's NCHW/OIHW and
transposes at the boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..util import getenv_bool, getenv_str

__all__ = ["conv3x3_bwd_fused", "fused_eligible", "conv3x3_custom"]

_ACC = jnp.float32


def _compiler_params(pltpu):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _interpret():
    return jax.default_backend() != "tpu"


def _block_n(h, c, n):
    """Batch-block size for the patch kernel: the two (bn,H,W,9C) patch
    scratches dominate; the in/out blocks are double-buffered on top.
    Stay under ~11MB of the 16MB scoped-vmem limit."""
    lanes = max(c, 128)
    lanes9 = -(-9 * c // 128) * 128
    per_img = (2 * h * h * lanes9 * 2          # x/go patch scratch bf16
               + 3 * h * h * lanes * 2 * 2)    # in x, in go, out dx, 2-buf
    budget = 11 * 1024 * 1024
    bn = max(1, budget // per_img)
    while n % bn:
        bn -= 1
    return bn


def _patch_kernel(x_ref, go_ref, wd_ref, dx_ref, dw_ref, xp_sc, gp_sc,
                  *, bn, h, w_sp, ci, co, prec):
    """im2col formulation: build (M, 9C) patch matrices in VMEM with 9
    slice-to-slice copies (zero halo implicit), then TWO big matmuls —
      dx (M,I)    = GOpatch (M,9O) . Wd (9O,I)        K = 9*O
      dW (9I,O)  += Xpatch^T (9I,M) . go_center (M,O) K = M
    K=9C keeps the MXU full where the 9-tap form ran K=C (25%% util at
    C=64). wd_ref is W pre-arranged as [(2-kh,2-kw,o), i] outside."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    xp_sc[...] = jnp.zeros_like(xp_sc)
    gp_sc[...] = jnp.zeros_like(gp_sc)
    for kh in range(3):
        for kw in range(3):
            t = kh * 3 + kw
            sh0, sh1 = max(0, 1 - kh), min(h, h + 1 - kh)
            sw0, sw1 = max(0, 1 - kw), min(w_sp, w_sp + 1 - kw)
            xp_sc[:, sh0:sh1, sw0:sw1, t * ci:(t + 1) * ci] = \
                x_ref[:, sh0 + kh - 1:sh1 + kh - 1,
                      sw0 + kw - 1:sw1 + kw - 1, :]
            gp_sc[:, sh0:sh1, sw0:sw1, t * co:(t + 1) * co] = \
                go_ref[:, sh0 + kh - 1:sh1 + kh - 1,
                       sw0 + kw - 1:sw1 + kw - 1, :]

    @pl.when(step == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    m = bn * h * w_sp
    xpat = xp_sc[...].reshape(m, 9 * ci)
    gpat = gp_sc[...].reshape(m, 9 * co)
    go_c = gpat[:, 4 * co:5 * co]
    dw_ref[...] += lax.dot_general(
        xpat, go_c, (((0,), (0,)), ((), ())),
        preferred_element_type=_ACC, precision=prec)
    dx = lax.dot_general(
        gpat, wd_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=_ACC, precision=prec)
    dx_ref[...] = dx.reshape(bn, h, w_sp, ci).astype(dx_ref.dtype)


def _bwd_kernel(x_ref, go_ref, w_ref, dx_ref, dw_ref, xp_sc, gp_sc,
                *, bn, h, w_sp, ci, co, prec):
    """One sequential grid step over a batch block. dw_ref is revisited
    by every step (index_map is constant) and accumulates in f32."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)

    # stage the block into zero-padded VMEM copies (halo = 1)
    xp_sc[...] = jnp.zeros_like(xp_sc)
    gp_sc[...] = jnp.zeros_like(gp_sc)
    xp_sc[:, 1:1 + h, 1:1 + w_sp, :] = x_ref[...]
    gp_sc[:, 1:1 + h, 1:1 + w_sp, :] = go_ref[...]

    @pl.when(step == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    m = bn * h * w_sp
    go_c = gp_sc[:, 1:1 + h, 1:1 + w_sp, :].reshape(m, co)

    dx_acc = jnp.zeros((m, ci), _ACC)
    for kh in range(3):
        for kw in range(3):
            xs = xp_sc[:, kh:kh + h, kw:kw + w_sp, :].reshape(m, ci)
            gs = gp_sc[:, 2 - kh:2 - kh + h,
                       2 - kw:2 - kw + w_sp, :].reshape(m, co)
            # dW[kh,kw] = x_shift^T . go_center  -> (ci, co)
            dw_ref[kh, kw] += lax.dot_general(
                xs, go_c, (((0,), (0,)), ((), ())),
                preferred_element_type=_ACC,
                precision=prec)
            # dx += go_shift . W[kh,kw]^T  (contract co) -> (m, ci)
            dx_acc += lax.dot_general(
                gs, w_ref[kh, kw], (((1,), (1,)), ((), ())),
                preferred_element_type=_ACC,
                precision=prec)
    dx_ref[...] = dx_acc.reshape(bn, h, w_sp, ci).astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn",))
def _patch_nhwc(x, go, w_hwio, bn):
    """Patch-matrix variant. w_hwio (3,3,I,O) is rearranged here to
    Wd[(2-kh)(2-kw)o, i] for the dx matmul; dW comes back as (9I, O)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, w_sp, ci = x.shape
    co = go.shape[-1]
    grid = (n // bn,)
    # Wd: tap t=(th,tw) row-block holds W[2-th, 2-tw] as (O, I)
    wd = jnp.flip(w_hwio, axis=(0, 1))            # [2-kh, 2-kw, i, o]
    wd = jnp.transpose(wd, (0, 1, 3, 2))           # [th, tw, o, i]
    wd = wd.reshape(9 * co, ci)
    prec = (lax.Precision.DEFAULT if x.dtype == jnp.bfloat16
            else lax.Precision.HIGHEST)
    kern = functools.partial(_patch_kernel, bn=bn, h=h, w_sp=w_sp,
                             ci=ci, co=co, prec=prec)
    params = _compiler_params(pltpu)
    dx, dw = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, h, w_sp, ci), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((bn, h, w_sp, co), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9 * co, ci), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h, w_sp, ci), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9 * ci, co), lambda i: (0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((n, h, w_sp, ci), x.dtype),
                   jax.ShapeDtypeStruct((9 * ci, co), _ACC)],
        scratch_shapes=[
            pltpu.VMEM((bn, h, w_sp, 9 * ci), x.dtype),
            pltpu.VMEM((bn, h, w_sp, 9 * co), go.dtype),
        ],
        compiler_params=params,
        interpret=_interpret(),
        name="conv3x3_bwd_patch",
    )(x, go, wd)
    # dw rows are [(kh,kw,i)]; back to (3,3,I,O)
    return dx, dw.reshape(3, 3, ci, co)


@functools.partial(jax.jit, static_argnames=("bn",))
def _bwd_nhwc(x, go, w_hwio, bn):
    """x (N,H,W,I), go (N,H,W,O), w (3,3,I,O) -> dx (N,H,W,I),
    dw (3,3,I,O) f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, w_sp, ci = x.shape
    co = go.shape[-1]
    grid = (n // bn,)
    # bf16 operands: DEFAULT is mandatory (Mosaic rejects the implicit
    # contract_precision<fp32>); f32 operands: HIGHEST keeps true-f32
    # dots, matching the XLA conv vjp (DEFAULT would round to bf16)
    prec = (lax.Precision.DEFAULT if x.dtype == jnp.bfloat16
            else lax.Precision.HIGHEST)
    kern = functools.partial(_bwd_kernel, bn=bn, h=h, w_sp=w_sp,
                             ci=ci, co=co, prec=prec)
    params = _compiler_params(pltpu)
    dx, dw = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, h, w_sp, ci), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((bn, h, w_sp, co), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, ci, co), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h, w_sp, ci), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, ci, co), lambda i: (0, 0, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((n, h, w_sp, ci), x.dtype),
                   jax.ShapeDtypeStruct((3, 3, ci, co), _ACC)],
        scratch_shapes=[
            pltpu.VMEM((bn, h + 2, w_sp + 2, ci), x.dtype),
            pltpu.VMEM((bn, h + 2, w_sp + 2, co), go.dtype),
        ],
        compiler_params=params,
        interpret=_interpret(),
        name="conv3x3_bwd_taps",
    )(x, go, w_hwio)
    return dx, dw


def conv3x3_bwd_fused(x, w, go, bn=None):
    """Fused conv backward. x (N,I,H,W) NCHW, w (O,I,3,3) OIHW,
    go (N,O,H,W). Returns (dx NCHW, dw OIHW, None-bias-grad omitted)."""
    n, ci, h, w_sp = x.shape
    co = w.shape[0]
    if bn is None:
        bn = _block_n(h, max(ci, co), n)
    xt = jnp.transpose(x, (0, 2, 3, 1))
    gt = jnp.transpose(go, (0, 2, 3, 1))
    w_hwio = jnp.transpose(w, (2, 3, 1, 0))
    if getenv_str("MXTPU_CONV_BWD_KERNEL") == "taps":
        dx, dw = _bwd_nhwc(xt, gt, w_hwio, bn)
    else:
        dx, dw = _patch_nhwc(xt, gt, w_hwio, bn)
    return (jnp.transpose(dx, (0, 3, 1, 2)),
            jnp.transpose(dw, (3, 2, 0, 1)).astype(w.dtype))


def fused_eligible(data_shape, w_shape, kernel, stride, dilate, pad,
                   num_group):
    """3x3 stride-1 pad-1 ungrouped 2D conv on TPU with even batch."""
    if not getenv_bool("MXTPU_FUSED_CONV_BWD"):
        # default OFF: measured slower than XLA's native conv backward at
        # every ResNet shape on v5e (docs/perf_notes.md round-4 section)
        return False
    return (len(kernel) == 2 and tuple(kernel) == (3, 3)
            and tuple(stride) == (1, 1) and tuple(dilate) == (1, 1)
            and tuple(pad) == (1, 1) and num_group == 1
            and len(data_shape) == 4)


@jax.custom_vjp
def conv3x3_custom(x, w):
    """3x3 s1 p1 conv whose vjp is the fused Pallas backward."""
    return _conv3x3_fwd_impl(x, w)


def _conv3x3_fwd_impl(x, w):
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[(1, 1), (1, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=jnp.float32 if x.dtype == jnp.float32
        else None).astype(x.dtype)


def _conv3x3_fwd(x, w):
    return _conv3x3_fwd_impl(x, w), (x, w)


def _conv3x3_bwd(res, go):
    x, w = res
    dx, dw = conv3x3_bwd_fused(x, w, go.astype(x.dtype))[:2]
    return dx, dw


conv3x3_custom.defvjp(_conv3x3_fwd, _conv3x3_bwd)


# ---------------------------------------------------------------------------
# autotuner registration (PR: tuned dispatch replaces the static
# fused_eligible heuristic at the Convolution call site)
# ---------------------------------------------------------------------------

def _conv3x3_bench(fn, x, w):
    """One timed repetition = forward + full vjp: conv3x3_custom's forward
    IS the XLA conv — only the backward differs — so a fair race times the
    gradient sweep, and the tuner's output check covers grad parity."""
    out, vjp = jax.vjp(fn, x, w)
    dx, dw = vjp(jnp.ones_like(out))
    return out, dx, dw


def conv3x3_candidates(args, kwargs):
    """Tuner search space for the 3x3 s1 p1 conv: the fused Pallas
    backward raced against XLA's native vjp. Eligibility still honors the
    MXTPU_FUSED_CONV_BWD opt-in (the kernel is the documented
    measured-negative on v5e), but selection is now by measurement — the
    kernel is only dispatched on shapes where it actually won the race."""
    del kwargs
    x, w = args[0], args[1]
    if not fused_eligible(tuple(x.shape), tuple(w.shape), (3, 3), (1, 1),
                          (1, 1), (1, 1), 1):
        return {}
    if _interpret() and not getenv_bool("MXTPU_TUNE_INTERPRET"):
        # interpret-mode pallas always loses a fair race; don't time it
        return {}
    return {"pallas_bwd": conv3x3_custom}


def _register_tuned():
    from .. import tune
    tune.register_kernel("conv3x3", conv3x3_candidates, version=1,
                         bench=_conv3x3_bench)


_register_tuned()
