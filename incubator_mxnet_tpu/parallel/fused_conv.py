"""Fused conv+BN+ReLU forward and BN-apply(+add)+ReLU epilogue Pallas
kernels, registered as autotuner candidates (tune.py).

The reference fused these chains in cuDNN (conv + bias + activation via
cudnnConvolutionBiasActivationForward; BN-add-relu in the NHWC batchnorm
kernels, src/operator/nn/cudnn/).  On TPU, XLA already fuses elementwise
epilogues into convs MOST of the time — so unlike the reference, nothing
here is dispatched unconditionally: every kernel is a CANDIDATE the
autotuner times against the plain-XLA composition per (shape, dtype,
device), and the loser is never called.  A train step takes the XLA
candidate at every site without a race (parallel/train.py traces its
forward pass under tune.xla_only), so the race runs where a forward pass
is the whole program: Predictor and eager ops.

Two kernel families:

* ``conv_bn_relu``: k x k STRIDE-1 same-size conv (asymmetric pad
  allowed, covering both 3x3 p1 residual convs and the 4x4 pad-(2,1)
  conv the MLPerf space-to-depth stem rewrite produces) with the BN
  scale/bias apply and ReLU fused into the accumulator epilogue — one
  HBM pass instead of conv-out + BN-read + ReLU-read.  Two formulations
  share the search space: ``taps`` (k^2 shifted K=C matmuls on a padded
  VMEM copy) and ``patch`` (im2col in VMEM, one K=k^2*C matmul), times a
  batch-block ladder.
* ``bn_act``/``bn_add_act``/``bn_apply``: the BN multiply-add epilogue
  with optional residual add and optional ReLU as a flat (rows, C)
  elementwise kernel — the train-path fusion, where batch statistics
  force the conv output to materialize first.

Numerics replicate ops/nn_ops.py exactly IN ORDER: f32 accumulate, cast
to the data dtype (the Convolution op's trailing astype), re-promote to
f32 for scale/bias, cast back, THEN residual-add and ReLU in the data
dtype.  Gradients come from ``jax.custom_vjp`` whose backward is the
``jax.vjp`` of the reference XLA composition — exact parity with the
unfused path by construction, no hand backward kernel to drift.

Layout: NHWC inside (channel-minor = MXU/VPU lane dim), NCHW at the
boundary.  Off-TPU the kernels run in interpret mode, but are only
OFFERED to the tuner under MXTPU_TUNE_INTERPRET (interpret mode always
loses a fair race; tests set it).
"""
from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax import lax

# the references (each family's implicit "xla" candidate, and the oracle
# the kernels' backward is the vjp of) are the op layer's own compositions
from ..ops.nn_ops import bn_act_reference, conv_bn_relu_reference
from ..util import getenv_bool

__all__ = ["bn_act_reference", "conv_bn_relu_reference",
           "bn_act_candidates", "conv_bn_relu_candidates",
           "register_kernels"]

_ACC = jnp.float32
_VMEM_BUDGET = 11 * 1024 * 1024     # of the ~16MB scoped-vmem window
# _conv_vmem counts what the compiler allocates, so it may sit closer
_CONV_VMEM_BUDGET = 14 * 1024 * 1024


def _compiler_params(pltpu):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _interpret():
    return jax.default_backend() != "tpu"


def _prec(dtype):
    # bf16 operands: DEFAULT is mandatory (Mosaic rejects the implicit
    # fp32 contract); f32: HIGHEST keeps true-f32 dots like the XLA conv
    return (lax.Precision.DEFAULT if dtype == jnp.bfloat16
            else lax.Precision.HIGHEST)


def _lanes(c):
    return -(-c // 128) * 128


# ---------------------------------------------------------------------------
# BN epilogue kernel: rows x channels elementwise multiply-add(+add)(+relu)
# ---------------------------------------------------------------------------

def _epi_kernel(z_ref, s_ref, b_ref, o_ref, *, relu):
    y = (z_ref[...].astype(_ACC) * s_ref[...] + b_ref[...]).astype(o_ref.dtype)
    o_ref[...] = jnp.maximum(y, 0) if relu else y


def _epi_res_kernel(z_ref, s_ref, b_ref, r_ref, o_ref, *, relu):
    y = (z_ref[...].astype(_ACC) * s_ref[...] + b_ref[...]).astype(o_ref.dtype)
    y = y + r_ref[...]
    o_ref[...] = jnp.maximum(y, 0) if relu else y


@functools.partial(jax.jit, static_argnames=("bm", "relu"))
def _epi_rows(z2, s2, b2, bm, relu):
    from jax.experimental import pallas as pl
    m, c = z2.shape
    return pl.pallas_call(
        functools.partial(_epi_kernel, relu=relu),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, c), z2.dtype),
        interpret=_interpret(),
        name="bn_act" if relu else "bn_apply",
    )(z2, s2, b2)


@functools.partial(jax.jit, static_argnames=("bm", "relu"))
def _epi_res_rows(z2, s2, b2, r2, bm, relu):
    from jax.experimental import pallas as pl
    m, c = z2.shape
    return pl.pallas_call(
        functools.partial(_epi_res_kernel, relu=relu),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((bm, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, c), z2.dtype),
        interpret=_interpret(),
        name="bn_add_act",
    )(z2, s2, b2, r2)


def _to_rows(z):
    n, c, h, w = z.shape
    return jnp.transpose(z, (0, 2, 3, 1)).reshape(n * h * w, c)


def _from_rows(z2, shape):
    n, c, h, w = shape
    return jnp.transpose(z2.reshape(n, h, w, c), (0, 3, 1, 2))


def _epi_impl(z, scale, bias, residual, bm, relu):
    c = z.shape[1]
    s2 = scale.astype(_ACC).reshape(1, c)
    b2 = bias.astype(_ACC).reshape(1, c)
    if residual is None:
        out = _epi_rows(_to_rows(z), s2, b2, bm, relu)
    else:
        out = _epi_res_rows(_to_rows(z), s2, b2, _to_rows(residual), bm, relu)
    return _from_rows(out, z.shape)


@functools.lru_cache(maxsize=None)
def _make_bn_act(bm, with_res, relu):
    """custom_vjp wrapper for one epilogue config: Pallas forward, XLA
    reference-vjp backward (gradient parity by construction)."""
    if with_res:
        @jax.custom_vjp
        def f(z, scale, bias, residual):
            return _epi_impl(z, scale, bias, residual, bm, relu)

        def fwd(z, scale, bias, residual):
            return f(z, scale, bias, residual), (z, scale, bias, residual)

        def bwd(res, g):
            z, scale, bias, residual = res
            _, vjp = jax.vjp(
                lambda a, s, b, r: bn_act_reference(a, s, b, r, relu=relu),
                z, scale, bias, residual)
            return vjp(g)
    else:
        @jax.custom_vjp
        def f(z, scale, bias):
            return _epi_impl(z, scale, bias, None, bm, relu)

        def fwd(z, scale, bias):
            return f(z, scale, bias), (z, scale, bias)

        def bwd(res, g):
            z, scale, bias = res
            _, vjp = jax.vjp(
                lambda a, s, b: bn_act_reference(a, s, b, relu=relu),
                z, scale, bias)
            return vjp(g)
    f.defvjp(fwd, bwd)
    return f


def _row_blocks(m, c, itemsize, n_blocks=2):
    """Batch-row block ladder for the epilogue: aligned divisors of m,
    largest first, sized to keep in+out+residual blocks under budget."""
    out = []
    for bm in (16384, 8192, 4096, 2048, 1024, 512, 128, 32, 16, 8):
        if m % bm or bm > m:
            continue
        if 3 * bm * _lanes(c) * itemsize > _VMEM_BUDGET:
            continue
        out.append(bm)
        if len(out) >= n_blocks:
            break
    if not out and m * 3 * _lanes(c) * itemsize <= _VMEM_BUDGET:
        out.append(m)    # single block: tiny activations
    return out


def _epi_shape_ok(z, scale):
    return (z.ndim == 4 and scale.ndim == 1
            and z.shape[1] == scale.shape[0]
            and z.dtype in (jnp.float32, jnp.bfloat16))


def _offer_pallas():
    return not _interpret() or getenv_bool("MXTPU_TUNE_INTERPRET")


def bn_act_candidates(relu, with_res):
    """Builder factory for the bn_act / bn_add_act / bn_apply families."""
    def build(args, kwargs):
        del kwargs
        z, scale = args[0], args[1]
        residual = args[3] if with_res else None
        if not _offer_pallas() or not _epi_shape_ok(z, scale):
            return {}
        if with_res and (residual is None or residual.shape != z.shape):
            return {}
        n, c, h, w = z.shape
        m = n * h * w
        cands = OrderedDict()
        for bm in _row_blocks(m, c, jnp.dtype(z.dtype).itemsize):
            fn = _make_bn_act(bm, with_res, relu)
            cands[f"pallas_bm{bm}"] = fn
        return cands
    return build


# ---------------------------------------------------------------------------
# fused conv+BN+ReLU forward kernel (k x k stride-1, same-size output)
# ---------------------------------------------------------------------------

def _conv_taps_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, xp_sc, *,
                      bn, h, w_sp, ci, co, k, plo_h, plo_w, prec):
    """k^2 shifted K=C matmuls against a zero-padded VMEM copy of the
    input block; BN scale/bias + ReLU applied on the f32 accumulator."""
    xp_sc[...] = jnp.zeros_like(xp_sc)
    xp_sc[:, plo_h:plo_h + h, plo_w:plo_w + w_sp, :] = x_ref[...]
    m = bn * h * w_sp
    acc = jnp.zeros((m, co), _ACC)
    for kh in range(k):
        for kw in range(k):
            xs = xp_sc[:, kh:kh + h, kw:kw + w_sp, :].reshape(m, ci)
            acc += lax.dot_general(
                xs, w_ref[kh, kw], (((1,), (0,)), ((), ())),
                preferred_element_type=_ACC, precision=prec)
    z = acc.astype(o_ref.dtype).astype(_ACC)
    y = (z * s_ref[...] + b_ref[...]).astype(o_ref.dtype)
    o_ref[...] = jnp.maximum(y, 0).reshape(bn, h, w_sp, co)


def _conv_patch_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, xp_sc, pat_sc, *,
                       bn, h, w_sp, ci, co, k, plo_h, plo_w, prec):
    """im2col formulation: (M, k^2*C) patch matrix in VMEM, ONE matmul
    (K=k^2*C keeps the MXU full at small C), fused BN+ReLU epilogue."""
    xp_sc[...] = jnp.zeros_like(xp_sc)
    xp_sc[:, plo_h:plo_h + h, plo_w:plo_w + w_sp, :] = x_ref[...]
    for kh in range(k):
        for kw in range(k):
            t = kh * k + kw
            pat_sc[:, :, :, t * ci:(t + 1) * ci] = \
                xp_sc[:, kh:kh + h, kw:kw + w_sp, :]
    m = bn * h * w_sp
    acc = lax.dot_general(
        pat_sc[...].reshape(m, k * k * ci), w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=_ACC, precision=prec)
    z = acc.astype(o_ref.dtype).astype(_ACC)
    y = (z * s_ref[...] + b_ref[...]).astype(o_ref.dtype)
    o_ref[...] = jnp.maximum(y, 0).reshape(bn, h, w_sp, co)


@functools.partial(jax.jit, static_argnames=("bn", "k", "plo_h", "plo_w",
                                             "variant"))
def _conv_bn_relu_nhwc(x, w_hwio, s2, b2, *, bn, k, plo_h, plo_w, variant):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, w_sp, ci = x.shape
    co = w_hwio.shape[-1]
    hp = h + k - 1
    wp = w_sp + k - 1
    prec = _prec(x.dtype)
    params = _compiler_params(pltpu)
    common = dict(bn=bn, h=h, w_sp=w_sp, ci=ci, co=co, k=k,
                  plo_h=plo_h, plo_w=plo_w, prec=prec)
    if variant == "patch":
        kern = functools.partial(_conv_patch_kernel, **common)
        wmat = w_hwio.reshape(k * k * ci, co)
        w_spec = pl.BlockSpec((k * k * ci, co), lambda i: (0, 0))
        scratch = [pltpu.VMEM((bn, hp, wp, ci), x.dtype),
                   pltpu.VMEM((bn, h, w_sp, k * k * ci), x.dtype)]
    else:
        kern = functools.partial(_conv_taps_kernel, **common)
        wmat = w_hwio
        w_spec = pl.BlockSpec((k, k, ci, co), lambda i: (0, 0, 0, 0))
        scratch = [pltpu.VMEM((bn, hp, wp, ci), x.dtype)]
    return pl.pallas_call(
        kern,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, h, w_sp, ci), lambda i: (i, 0, 0, 0)),
            w_spec,
            pl.BlockSpec((1, co), lambda i: (0, 0)),
            pl.BlockSpec((1, co), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, h, w_sp, co), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h, w_sp, co), x.dtype),
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=_interpret(),
        name=f"conv_bn_relu_{variant}",
    )(x, wmat, s2, b2)


def _conv_impl(x, w, scale, bias, k, plo_h, plo_w, bn, variant):
    co = w.shape[0]
    xt = jnp.transpose(x, (0, 2, 3, 1))
    w_hwio = jnp.transpose(w, (2, 3, 1, 0))
    s2 = scale.astype(_ACC).reshape(1, co)
    b2 = bias.astype(_ACC).reshape(1, co)
    out = _conv_bn_relu_nhwc(xt, w_hwio, s2, b2, bn=bn, k=k, plo_h=plo_h,
                             plo_w=plo_w, variant=variant)
    return jnp.transpose(out, (0, 3, 1, 2))


@functools.lru_cache(maxsize=None)
def _make_conv_bn_relu(k, pad_lo, pad_hi, bn, variant):
    """custom_vjp wrapper for one fused-conv config; the backward is the
    jax.vjp of the XLA reference (rematerializes the conv output — all
    plain XLA ops, exact parity with the unfused gradient)."""
    @jax.custom_vjp
    def f(x, w, scale, bias):
        return _conv_impl(x, w, scale, bias, k, pad_lo[0], pad_lo[1],
                          bn, variant)

    def fwd(x, w, scale, bias):
        return f(x, w, scale, bias), (x, w, scale, bias)

    def bwd(res, g):
        x, w, scale, bias = res
        _, vjp = jax.vjp(
            lambda a, b, s, c: conv_bn_relu_reference(a, b, s, c, k,
                                                      pad_lo, pad_hi),
            x, w, scale, bias)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def _conv_vmem(bn, h, w_sp, ci, co, k, itemsize, variant):
    """Scoped-VMEM bytes one grid step needs, counted the way Mosaic
    allocates (checked against the v5e compiler at every ResNet-50
    shape): the W axis pads to the sublane tile, channels to 128 lanes,
    the pipelined in/out blocks are double-buffered, and the patch
    matrix exists three times — the scratch, its loaded value, and the
    (M, k*k*C) relayout the matmul consumes."""
    sub = 32 // itemsize

    def rows(n):
        return -(-n // sub) * sub

    hp, wp = h + k - 1, w_sp + k - 1
    px = bn * h * rows(w_sp)
    pad_copy = bn * hp * rows(wp) * _lanes(ci) * itemsize
    blocks = 2 * px * (_lanes(ci) + _lanes(co)) * itemsize
    weights = k * k * rows(ci) * _lanes(co) * itemsize
    acc = 2 * px * _lanes(co) * 4          # f32 accumulator + epilogue
    total = pad_copy + blocks + weights + acc
    if variant == "patch":
        total += 3 * px * _lanes(k * k * ci) * itemsize
    else:
        total += 2 * px * _lanes(ci) * itemsize   # the shifted tap operand
    return total


def _conv_shape_ok(x, w, k, pad_lo, pad_hi):
    if x.ndim != 4 or w.ndim != 4:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16) or w.dtype != x.dtype:
        return False
    if w.shape[2] != k or w.shape[3] != k or w.shape[1] != x.shape[1]:
        return False
    # stride-1 same-size outputs only: total pad must rebuild k-1
    return (pad_lo[0] + pad_hi[0] == k - 1 and pad_lo[1] + pad_hi[1] == k - 1)


def conv_bn_relu_candidates(args, kwargs):
    """Tuner search space for the fused forward: {taps, patch} x a batch
    block ladder, pruned by the VMEM budget."""
    x, w = args[0], args[1]
    k = kwargs["k"]
    pad_lo = tuple(kwargs["pad_lo"])
    pad_hi = tuple(kwargs["pad_hi"])
    if not _offer_pallas() or not _conv_shape_ok(x, w, k, pad_lo, pad_hi):
        return {}
    n, ci, h, w_sp = x.shape
    co = w.shape[0]
    itemsize = jnp.dtype(x.dtype).itemsize
    cands = OrderedDict()
    for variant in ("patch", "taps"):
        added = 0
        for bn in (8, 4, 2, 1):
            if n % bn or added >= 2:
                continue
            if _conv_vmem(bn, h, w_sp, ci, co, k, itemsize,
                          variant) > _CONV_VMEM_BUDGET:
                continue
            fn = _make_conv_bn_relu(k, pad_lo, pad_hi, bn, variant)
            cands[f"pallas_{variant}_bn{bn}"] = \
                _strip_kwargs(fn)
            added += 1
    return cands


def _strip_kwargs(fn):
    # tuned_call forwards the call-site kwargs (k/pad_lo/pad_hi) to every
    # candidate; the factory already baked them in as statics
    def call(x, w, scale, bias, **kwargs):
        del kwargs
        return fn(x, w, scale, bias)
    return call


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------

def register_kernels():
    """Register the fused-kernel search spaces with the autotuner (runs at
    module import; idempotent — re-registering replaces same-name specs)."""
    from .. import tune
    tune.register_kernel("conv_bn_relu", conv_bn_relu_candidates, version=1)
    tune.register_kernel("bn_act", bn_act_candidates(True, False), version=1)
    tune.register_kernel("bn_add_act", bn_act_candidates(True, True),
                         version=1)
    tune.register_kernel("bn_apply", bn_act_candidates(False, False),
                         version=1)


register_kernels()
