"""Neural-network operators: conv, pooling, dense, norms, activations, dropout.

Reference: src/operator/nn/ (28,295 LoC — Convolution/FullyConnected/BatchNorm/
Pooling/Softmax/Activation/Dropout/LayerNorm/... plus cuDNN/MKL-DNN wrapper
trees). TPU-native redesign: every op is a single XLA-lowerable jax function —
convolution is `lax.conv_general_dilated` (XLA tiles it onto the MXU directly;
there is no im2col/cudnn-algo-select analog), pooling is `lax.reduce_window`,
and normalization/activation ops are elementwise chains XLA fuses into
neighboring matmuls, which is the TPU replacement for the reference's
hand-fused cuDNN kernels.
"""
from __future__ import annotations

import functools

import numpy as _np

from ..base import MXNetError, dtype_np
from .. import tune
from .registry import register

import jax
import jax.numpy as jnp
from jax import lax


# --------------------------------------------------------------------------
# FullyConnected (reference src/operator/nn/fully_connected.cc:245-333)
# --------------------------------------------------------------------------

@register(name="FullyConnected", aliases=("fully_connected",))
def fully_connected(data, weight, bias=None, *, num_hidden=0, no_bias=False,
                    flatten=True):
    x = data
    if flatten and x.ndim > 2:
        x = jnp.reshape(x, (x.shape[0], -1))
    elif not flatten and x.ndim > 2:
        pass  # apply to last axis
    out = jnp.matmul(x, weight.T) if x.ndim <= 2 else jnp.einsum("...i,oi->...o", x, weight)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# --------------------------------------------------------------------------
# Convolution / Deconvolution (reference src/operator/nn/convolution.cc,
# deconvolution.cc; im2col.cuh / depthwise_convolution_tf.cuh have no analog —
# XLA handles layout + MXU tiling)
# --------------------------------------------------------------------------

def _conv_dnums(nd_):
    # MXNet layouts are channel-first: NCW / NCHW / NCDHW.
    spatial = "WHD"[:nd_][::-1] if nd_ > 1 else "W"
    spatial = {1: "W", 2: "HW", 3: "DHW"}[nd_]
    return lax.conv_dimension_numbers(
        (1, 1) + (1,) * nd_, (1, 1) + (1,) * nd_,
        ("NC" + spatial, "OI" + spatial, "NC" + spatial))


def _tup(v, n, default):
    if v is None or (hasattr(v, "__len__") and len(v) == 0):
        return (default,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(int(x) for x in v)


def _stem_s2d_parts(data, weight, k):
    """The space-to-depth input/weight transforms plus the equivalent
    stride-1 conv geometry (m, pad lo/hi), shared by _stem_s2d_conv and
    the fused conv+BN+ReLU inference path."""
    n, c, h, w = data.shape
    x = data.reshape(n, c, h // 2, 2, w // 2, 2)
    x = x.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * 4, h // 2, w // 2)
    o = weight.shape[0]
    m = (k + 1) // 2
    wp = jnp.pad(weight, ((0, 0), (0, 0), (1, 0), (1, 0)))
    wp = wp.reshape(o, c, m, 2, m, 2)
    wp = wp.transpose(0, 1, 3, 5, 2, 4).reshape(o, c * 4, m, m)
    lo = (k // 2 + 1) // 2
    hi = (k - k // 2 - 2) // 2
    return x, wp, m, lo, hi


def _stem_s2d_conv(data, weight, k):
    """Space-to-depth rewrite of a k x k stride-2 'same' conv on a skinny
    channel input (the ResNet/Inception stem shape): 2x2 space-to-depth on
    the input, the kernel zero-padded to (k+1) and folded the same way,
    then an m x m STRIDE-1 conv (m = (k+1)/2) on 4x the channels.

    Mathematically identical (the MLPerf conv0 space-to-depth trick); on
    TPU it replaces a C_in=3 conv — which wastes 125/128 of every MXU pass
    — with a C_in=12 stride-1 conv XLA tiles far better. Exact only for
    k % 4 == 3 (pad k//2 odd), stride 2, dilation 1, groups 1, even H/W.
    """
    x, wp, _, lo, hi = _stem_s2d_parts(data, weight, k)
    dn = _conv_dnums(2)
    return lax.conv_general_dilated(
        x, wp, window_strides=(1, 1), padding=[(lo, hi), (lo, hi)],
        dimension_numbers=dn,
        preferred_element_type=jnp.float32 if data.dtype == jnp.float32
        else None)


def _stem_eligible(data, kernel, stride, dilate, pad, num_group):
    """The _stem_s2d_conv exactness conditions (see its docstring)."""
    return (len(kernel) == 2 and num_group == 1 and stride == (2, 2)
            and dilate == (1, 1) and kernel[0] == kernel[1]
            and kernel[0] % 4 == 3 and pad == (kernel[0] // 2,) * 2
            and data.ndim == 4 and data.shape[1] <= 8
            and data.shape[2] % 2 == 0 and data.shape[3] % 2 == 0
            and jax.default_backend() == "tpu")


def _conv_xla(data, weight, kernel, stride, dilate, pad, num_group):
    nd_ = len(kernel)
    dn = _conv_dnums(nd_)
    return lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        lhs_dilation=(1,) * nd_,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=jnp.float32 if data.dtype == jnp.float32
        else None)


def _conv_core(data, weight, kernel, stride, dilate, pad, num_group):
    """Convolution dispatch shared by the Convolution op and the fused
    conv+BN+ReLU paths: the stem space-to-depth rewrite where it is
    exact, else plain XLA."""
    kernel = tuple(int(x) for x in kernel)
    if _stem_eligible(data, kernel, stride, dilate, pad, num_group):
        return _stem_s2d_conv(data, weight, kernel[0])
    return _conv_xla(data, weight, kernel, stride, dilate, pad, num_group)


@register(name="Convolution", aliases=("convolution", "Convolution_v1"))
def convolution(data, weight, bias=None, *, kernel, stride=(), dilate=(), pad=(),
                num_filter=0, num_group=1, workspace=1024, no_bias=False,
                cudnn_tune=None, cudnn_off=False, layout=None):
    nd_ = len(kernel)
    stride = _tup(stride, nd_, 1)
    dilate = _tup(dilate, nd_, 1)
    pad = _tup(pad, nd_, 0)
    out = _conv_core(data, weight, kernel, stride, dilate, pad, num_group)
    if bias is not None and not no_bias:
        out = out + jnp.reshape(bias, (1, -1) + (1,) * nd_)
    return out.astype(data.dtype)


@register(name="Deconvolution", aliases=("deconvolution",))
def deconvolution(data, weight, bias=None, *, kernel, stride=(), dilate=(), pad=(),
                  adj=(), target_shape=(), num_filter=0, num_group=1,
                  workspace=512, no_bias=True, cudnn_tune=None, cudnn_off=False,
                  layout=None):
    """Transposed convolution = gradient of Convolution w.r.t. its input
    (reference src/operator/nn/deconvolution-inl.h)."""
    nd_ = len(kernel)
    stride = _tup(stride, nd_, 1)
    dilate = _tup(dilate, nd_, 1)
    pad = _tup(pad, nd_, 0)
    adj = _tup(adj, nd_, 0)
    dn = _conv_dnums(nd_)
    # weight layout for deconv in MXNet: (C_in, C_out/group, *kernel)
    out = lax.conv_general_dilated(
        data, jnp.flip(jnp.swapaxes(weight, 0, 1), axis=tuple(range(2, 2 + nd_))),
        window_strides=(1,) * nd_,
        padding=[(dilate[i] * (kernel[i] - 1) - pad[i],
                  dilate[i] * (kernel[i] - 1) - pad[i] + adj[i]) for i in range(nd_)],
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + jnp.reshape(bias, (1, -1) + (1,) * nd_)
    return out.astype(data.dtype)


# --------------------------------------------------------------------------
# Pooling (reference src/operator/nn/pooling.cc, pool.h/pool.cuh)
# --------------------------------------------------------------------------

@register(name="Pooling", aliases=("pooling", "Pooling_v1"))
def pooling(data, *, kernel=(), pool_type="max", global_pool=False, stride=(),
            pad=(), pooling_convention="valid", count_include_pad=True,
            cudnn_off=False, p_value=2, layout=None):
    nd_ = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * nd_
        pad = (0,) * nd_
    else:
        kernel = _tup(kernel, nd_, 1)
        stride = _tup(stride, nd_, 1)
        pad = _tup(pad, nd_, 0)

    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    if pooling_convention == "full" and not global_pool:
        # ceil output size (reference pooling-inl.h kFull): widen right pad.
        extra = []
        for i in range(nd_):
            insz = data.shape[2 + i] + 2 * pad[i]
            rem = (insz - kernel[i]) % stride[i]
            extra.append((stride[i] - rem) % stride[i] if rem else 0)
        pads = [(0, 0), (0, 0)] + [(pad[i], pad[i] + extra[i]) for i in range(nd_)]
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]

    # NOTE: init values must be weak-typed python scalars — jax's
    # reduce_window autodiff rule does not linearize with array inits.
    if pool_type == "max":
        # int pools (the quantized path) need a dtype-exact init scalar;
        # float pools keep the weak python scalar (see NOTE above)
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else _np.dtype(data.dtype).type(jnp.iinfo(data.dtype).min)
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0., lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return (s / denom).astype(data.dtype)
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0., lax.add, window, strides, pads)
        return (s / cnt).astype(data.dtype)
    if pool_type == "lp":
        pw = lax.reduce_window(jnp.abs(data) ** p_value, 0., lax.add,
                               window, strides, pads)
        return (pw ** (1.0 / p_value)).astype(data.dtype)
    raise MXNetError(f"unknown pool_type {pool_type}")


# --------------------------------------------------------------------------
# Normalization (reference src/operator/nn/batch_norm.cc, layer_norm.cc,
# group_norm.cc, instance_norm.cc, lrn.cc)
# --------------------------------------------------------------------------

def _bn_batch_stats(data, red):
    """Batch (mean, var) in f32 over reduce axes ``red``.

    ONE pass over the full activation for both statistics: sibling
    sum/sum-of-squares reductions multi-output-fuse in XLA, where
    mean-then-var reads the (large) activation from HBM twice. f32
    accumulation regardless of input dtype (bf16 sums would lose
    mass at ResNet-scale reduction counts). The reductions run on
    data SHIFTED by a per-channel estimate taken from ONE slice of
    the reduce dims (a 1/N-cost pre-read): var is shift-invariant,
    and a shift within O(std) of the true mean kills the
    E[x^2]-E[x]^2 catastrophic cancellation for badly-centered
    activations (|mean| >> std) — unconditionally, unlike a
    moving_mean shift, which is garbage at cold start.

    The shift is a CONSTANT of the reduction, under `stop_gradient`:
    mean and var do not depend on it (d mean/dc = 1 - n/n, d var/dc =
    -2 s1/n + 2 dmean), so its cotangent is the rounding residue of
    an exact zero, which autodiff would still pad back into the batch
    and add to the activation's gradient. On a dp mesh (batch sharded,
    statistics of the global batch) the slice lives on one chip. What
    crosses chips per layer: forward f32[C] for the shift, then
    (s1, s2) as one all-reduce; backward the layer's two f32[C]
    reductions as one. The cotangent would add an f32[C] and a
    (1, C, H, W) map to every backward layer, and the map's
    all-reduce keeps XLA from fusing that backward pass into the
    convolutions round it.
    """
    n = 1
    for i in red:
        n *= data.shape[i]
    if n == 0:
        # 0-size batch: the shifted one-pass path below slices [0:1]
        # of an empty reduce axis (a TypeError); the plain reductions
        # keep the old NaN-stats-no-crash contract for this edge
        return (jnp.mean(data.astype(jnp.float32), axis=red),
                jnp.var(data.astype(jnp.float32), axis=red))
    first = lax.stop_gradient(lax.slice_in_dim(data, 0, 1, axis=red[0]))
    c = jnp.mean(first.astype(jnp.float32), axis=red, keepdims=True)
    shifted = data.astype(jnp.float32) - c
    s1 = jnp.sum(shifted, axis=red, dtype=jnp.float32)
    s2 = jnp.sum(jnp.square(shifted), axis=red, dtype=jnp.float32)
    dmean = s1 / n
    mean = jnp.reshape(c, (-1,)) + dmean
    var = jnp.maximum(s2 / n - jnp.square(dmean), 0.0)
    return mean, var


def _bn_scale_bias(gamma, beta, mean, var, eps, fix_gamma):
    """BN recomposed as one multiply-add epilogue (scale/bias are C-sized
    — the per-channel math costs nothing; the activation is touched
    once)."""
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    scale = g * jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    bias = beta - mean * scale
    return scale, bias


def bn_act_reference(data, scale, bias, residual=None, *, relu=True, ax=1):
    """The BN apply chain every BatchNorm op ends in, and the XLA candidate
    of the tuned epilogue families (parallel/fused_conv.py, whose kernels'
    backward is this function's vjp): one multiply-add, rounded to the
    data dtype BEFORE the optional residual add and ReLU, as the Gluon
    blocks compose them layer by layer."""
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    out = (data * jnp.reshape(scale, shape)
           + jnp.reshape(bias, shape)).astype(data.dtype)
    if residual is not None:
        out = out + residual
    return jnp.maximum(out, 0) if relu else out


# tuned_call hands its keyword arguments to every candidate, so the
# family without ReLU binds the flag here
_bn_apply_xla = functools.partial(bn_act_reference, relu=False)


def conv_bn_relu_reference(data, weight, scale, bias, k, pad_lo, pad_hi):
    """XLA candidate of the tuned fused forward: a k x k stride-1 NCHW
    conv with (possibly asymmetric) padding, the Convolution op's trailing
    astype, then bn_act_reference."""
    del k       # the weight carries it; the Pallas candidates need it static
    z = lax.conv_general_dilated(
        data, weight, window_strides=(1, 1),
        padding=list(zip(pad_lo, pad_hi)),
        dimension_numbers=_conv_dnums(2),
        preferred_element_type=jnp.float32 if data.dtype == jnp.float32
        else None).astype(data.dtype)
    return bn_act_reference(z, scale, bias)


def _bn_apply(data, scale, bias, ax):
    """The BN scale/bias apply, autotuned on the NCHW fast path."""
    if ax == 1 and data.ndim == 4:
        from ..parallel import fused_conv  # noqa: F401 — registers epilogues
        return tune.tuned_call("bn_apply", _bn_apply_xla, data, scale, bias)
    return bn_act_reference(data, scale, bias, relu=False, ax=ax)


@register(name="BatchNorm", aliases=("batch_norm", "BatchNorm_v1"), train_aware=True)
def batch_norm(data, gamma, beta, moving_mean, moving_var, *, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False, training=False):
    """Returns (out, batch_mean, batch_var); the Gluon layer owns the running-
    stat update (the reference op mutates moving_mean in-place inside the
    kernel — src/operator/nn/batch_norm.cc:417; functional here for XLA)."""
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    if training and not use_global_stats:
        mean, var = _bn_batch_stats(data, red)
        mean = mean.astype(moving_mean.dtype)
        var = var.astype(moving_var.dtype)
    else:
        mean, var = moving_mean, moving_var
    scale, bias = _bn_scale_bias(gamma, beta, mean, var, eps, fix_gamma)
    return (_bn_apply(data, scale, bias, ax), mean, var)


@register(name="FusedBNAddReLU", aliases=("fused_bn_add_relu",),
          train_aware=True)
def fused_bn_add_relu(data, gamma, beta, moving_mean, moving_var,
                      residual=None, *, eps=1e-3, momentum=0.9,
                      fix_gamma=True, use_global_stats=False, axis=1,
                      training=False):
    """BatchNorm + optional residual add + ReLU as ONE op, with the apply
    chain dispatched through the autotuned epilogue table (reference: the
    fused NHWC bn-add-relu kernels under src/operator/nn/batch_norm.cu).
    Same contract as BatchNorm — returns (out, batch_mean, batch_var) and
    the Gluon block owns the running-stat update. Numerics match the
    layer-by-layer composition exactly: the BN output is rounded to the
    data dtype BEFORE the residual add and ReLU."""
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    if training and not use_global_stats:
        mean, var = _bn_batch_stats(data, red)
        mean = mean.astype(moving_mean.dtype)
        var = var.astype(moving_var.dtype)
    else:
        mean, var = moving_mean, moving_var
    scale, bias = _bn_scale_bias(gamma, beta, mean, var, eps, fix_gamma)
    if ax == 1 and data.ndim == 4:
        from ..parallel import fused_conv  # noqa: F401 — registers epilogues
        if residual is None:
            out = tune.tuned_call("bn_act", bn_act_reference, data, scale,
                                  bias)
        else:
            out = tune.tuned_call("bn_add_act", bn_act_reference, data,
                                  scale, bias, residual)
    else:
        out = bn_act_reference(data, scale, bias, residual, ax=ax)
    return (out, mean, var)


def _conv_bn_relu_infer(data, weight, scale, bias, kernel, stride, dilate,
                        pad, num_group, residual):
    """Inference fused-forward dispatch: the moving stats are already
    folded into scale/bias, so the whole chain is ONE tuned kernel when
    the conv is stride-1 same-size (directly, or via the stem
    space-to-depth rewrite); anything else is conv + tuned epilogue."""
    k = kernel[0] if kernel else 0
    if residual is None and _stem_eligible(data, kernel, stride, dilate,
                                           pad, num_group):
        x2, w2, m, lo, hi = _stem_s2d_parts(data, weight, k)
        return tune.tuned_call("conv_bn_relu", conv_bn_relu_reference, x2,
                               w2, scale, bias, k=m, pad_lo=(lo, lo),
                               pad_hi=(hi, hi))
    if (residual is None and len(kernel) == 2 and kernel == (k, k)
            and k % 2 == 1 and stride == (1, 1) and dilate == (1, 1)
            and pad == (k // 2,) * 2 and num_group == 1 and data.ndim == 4):
        return tune.tuned_call("conv_bn_relu", conv_bn_relu_reference,
                               data, weight, scale, bias, k=k,
                               pad_lo=(k // 2,) * 2, pad_hi=(k // 2,) * 2)
    z = _conv_core(data, weight, kernel, stride, dilate, pad,
                   num_group).astype(data.dtype)
    if residual is None:
        return tune.tuned_call("bn_act", bn_act_reference, z, scale, bias)
    return tune.tuned_call("bn_add_act", bn_act_reference, z, scale, bias,
                           residual)


@register(name="FusedConvBNReLU", aliases=("fused_conv_bn_relu",),
          train_aware=True)
def fused_conv_bn_relu(data, weight, gamma, beta, moving_mean, moving_var,
                       residual=None, *, kernel, stride=(), dilate=(),
                       pad=(), num_filter=0, num_group=1, eps=1e-3,
                       momentum=0.9, fix_gamma=True, use_global_stats=False,
                       training=False):
    """Convolution + BatchNorm + (optional residual add) + ReLU as one op
    (reference: cudnnConvolutionBiasActivationForward in
    src/operator/nn/cudnn/). Inference folds the moving stats into a
    per-channel scale/bias and dispatches the autotuned fused forward
    kernel; training must materialize the conv output for the batch
    statistics, so it fuses the epilogue only. Returns (out, mean, var)
    with BatchNorm's contract."""
    nd_ = len(kernel)
    kernel = tuple(int(x) for x in kernel)
    stride = _tup(stride, nd_, 1)
    dilate = _tup(dilate, nd_, 1)
    pad = _tup(pad, nd_, 0)
    from ..parallel import fused_conv  # noqa: F401 — registers the kernels
    if not training or use_global_stats:
        scale, bias = _bn_scale_bias(gamma, beta, moving_mean, moving_var,
                                     eps, fix_gamma)
        out = _conv_bn_relu_infer(data, weight, scale, bias, kernel, stride,
                                  dilate, pad, num_group, residual)
        return (out, moving_mean, moving_var)
    z = _conv_core(data, weight, kernel, stride, dilate, pad,
                   num_group).astype(data.dtype)
    red = (0,) + tuple(range(2, z.ndim))
    mean, var = _bn_batch_stats(z, red)
    mean = mean.astype(moving_mean.dtype)
    var = var.astype(moving_var.dtype)
    scale, bias = _bn_scale_bias(gamma, beta, mean, var, eps, fix_gamma)
    if residual is None:
        out = tune.tuned_call("bn_act", bn_act_reference, z, scale, bias)
    else:
        out = tune.tuned_call("bn_add_act", bn_act_reference, z, scale, bias,
                              residual)
    return (out, mean, var)


@register(name="LayerNorm", aliases=("layer_norm",))
def layer_norm(data, gamma, beta, *, axis=-1, eps=1e-5, output_mean_var=False):
    ax = axis % data.ndim
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    out = (data - mean) * lax.rsqrt(var + eps) * jnp.reshape(gamma, shape) + \
        jnp.reshape(beta, shape)
    if output_mean_var:
        return (out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax))
    return out


@register(name="InstanceNorm", aliases=("instance_norm",))
def instance_norm(data, gamma, beta, *, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * jnp.reshape(gamma, shape) + \
        jnp.reshape(beta, shape)


@register(name="GroupNorm", aliases=("group_norm",))
def group_norm(data, gamma, beta, *, num_groups=1, eps=1e-5):
    b, c = data.shape[0], data.shape[1]
    x = jnp.reshape(data, (b, num_groups, c // num_groups) + data.shape[2:])
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    # gamma/beta are per-GROUP, shape (num_groups,), applied in the grouped
    # view (reference group_norm-inl.h:163-171 reshapes gamma to
    # (1, num_groups, 1, ...) against the temp grouped data shape)
    pshape = (1, num_groups) + (1,) * (x.ndim - 2)
    x = x * jnp.reshape(gamma, pshape) + jnp.reshape(beta, pshape)
    return jnp.reshape(x, data.shape)


@register(name="LRN", aliases=("lrn",))
def lrn(data, *, nsize, alpha=1e-4, beta=0.75, knorm=2.0):
    """Reference src/operator/nn/lrn.cc — cross-channel local response norm."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    window = jnp.stack([padded[:, i:i + data.shape[1]] for i in range(nsize)], 0).sum(0)
    return data / jnp.power(knorm + alpha * window / nsize, beta)


# --------------------------------------------------------------------------
# Activations (reference src/operator/nn/activation.cc, leaky_relu.cc)
# --------------------------------------------------------------------------

@register(name="Activation", aliases=("activation",))
def activation(data, *, act_type):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    raise MXNetError(f"unknown act_type {act_type}")


@register(name="LeakyReLU", aliases=("leaky_relu",), stateful=True)
def leaky_relu(data, gamma=None, *, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, rng=None):
    """Reference src/operator/leaky_relu.cc: leaky/prelu/rrelu/elu/selu/gelu."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        if g.ndim < data.ndim:
            g = jnp.reshape(g, (1, -1) + (1,) * (data.ndim - 2)) if g.size > 1 else g
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data > 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        # eval mode uses the mean slope (reference leaky_relu-inl.h)
        s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, s * data)
    raise MXNetError(f"unknown act_type {act_type}")


# --------------------------------------------------------------------------
# Softmax family (reference src/operator/nn/softmax.cc, softmax_output.cc)
# --------------------------------------------------------------------------

@register(name="softmax")
def softmax(data, length=None, *, axis=-1, temperature=None, dtype=None,
            use_length=False):
    x = data / temperature if temperature else data
    if length is not None and use_length:
        T = data.shape[axis]
        steps = jnp.arange(T)
        mask_shape = [1] * data.ndim
        mask_shape[axis] = T
        mask = steps.reshape(mask_shape) < jnp.expand_dims(length.astype(jnp.int32), axis)
        x = jnp.where(mask, x, -jnp.inf)
    out = jax.nn.softmax(x, axis=axis)
    if length is not None and use_length:
        out = jnp.where(mask, out, 0.0)
    return out.astype(dtype_np(dtype)) if dtype else out


@register(name="log_softmax")
def log_softmax(data, *, axis=-1, temperature=None, dtype=None, use_length=False):
    x = data / temperature if temperature else data
    out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(dtype_np(dtype)) if dtype else out


@register(name="softmin")
def softmin(data, *, axis=-1, temperature=None, dtype=None):
    return jax.nn.softmax(-(data / temperature if temperature else data), axis=axis)


@register(name="SoftmaxActivation")
def softmax_activation(data, *, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    from .tensor_ops import flatten
    return jax.nn.softmax(flatten.fn(data), axis=-1).reshape(data.shape)


@register(name="SoftmaxOutput", aliases=("softmax_output", "Softmax"))
def softmax_output(data, label, *, grad_scale=1.0, ignore_label=-1.0,
                   use_ignore=False, multi_output=False, preserve_shape=False,
                   normalization="null", smooth_alpha=0.0, out_grad=False):
    """The defining quirk of SoftmaxOutput (reference softmax_output-inl.h):
    backward ignores the incoming gradient and emits (p - onehot(label))."""
    axis = 1 if multi_output else -1

    @jax.custom_vjp
    def f(x, y):
        return jax.nn.softmax(x, axis=axis)

    def fwd(x, y):
        return f(x, y), (f(x, y), y)

    def bwd(res, g):
        out, y = res
        nclass = out.shape[axis]
        oh = jax.nn.one_hot(y.astype(jnp.int32), nclass, axis=axis)
        if smooth_alpha:
            oh = oh * (1 - smooth_alpha) + smooth_alpha / (nclass - 1) * (1 - oh)
        grad = out - oh
        if use_ignore:
            keep = (y != ignore_label).astype(out.dtype)
            grad = grad * jnp.expand_dims(keep, axis)
        if normalization == "valid" and use_ignore:
            denom = jnp.maximum(jnp.sum(y != ignore_label), 1).astype(out.dtype)
            grad = grad / denom
        elif normalization == "batch":
            grad = grad / out.shape[0]
        return (grad * grad_scale, jnp.zeros_like(y))

    f.defvjp(fwd, bwd)
    return f(data, label)


@register(name="softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    """Reference src/operator/loss_binary_op.cc."""
    logp = jax.nn.log_softmax(data, axis=-1)
    lbl = label.astype(jnp.int32)
    return -jnp.sum(jnp.take_along_axis(logp, lbl[:, None], axis=-1))


@register(name="LinearRegressionOutput", aliases=("linear_regression_output",))
def linear_regression_output(data, label, *, grad_scale=1.0):
    return _regression_output(data, label, grad_scale, "linear")


@register(name="MAERegressionOutput", aliases=("mae_regression_output",))
def mae_regression_output(data, label, *, grad_scale=1.0):
    return _regression_output(data, label, grad_scale, "mae")


@register(name="LogisticRegressionOutput", aliases=("logistic_regression_output",))
def logistic_regression_output(data, label, *, grad_scale=1.0):
    return _regression_output(data, label, grad_scale, "logistic")


def _regression_output(data, label, grad_scale, kind):
    """Reference src/operator/regression_output.cc: forward is identity /
    sigmoid; backward is (pred - label) / batch * grad_scale."""

    @jax.custom_vjp
    def f(x, y):
        return jax.nn.sigmoid(x) if kind == "logistic" else x

    def fwd(x, y):
        return f(x, y), (x, y)

    def bwd(res, g):
        x, y = res
        pred = jax.nn.sigmoid(x) if kind == "logistic" else x
        diff = pred - jnp.reshape(y, x.shape)
        if kind == "mae":
            diff = jnp.sign(diff)
        return (diff * grad_scale / x.shape[0], jnp.zeros_like(y))

    f.defvjp(fwd, bwd)
    return f(data, label)


# --------------------------------------------------------------------------
# Dropout (reference src/operator/nn/dropout.cc) — stateful (PRNG key)
# --------------------------------------------------------------------------

@register(name="Dropout", aliases=("dropout",), stateful=True, train_aware=True)
def dropout_op(data, *, p=0.5, mode="training", axes=(), cudnn_off=False,
               training=False, rng=None):
    if (not training and mode != "always") or p == 0:
        return data
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, tuple(shape)).astype(data.dtype) / keep
    return data * mask


# --------------------------------------------------------------------------
# Up/Down sampling (reference src/operator/nn/upsampling.cc,
# contrib/bilinear_resize.cc)
# --------------------------------------------------------------------------

@register(name="UpSampling")
def upsampling(*data, scale=1, sample_type="nearest", num_args=1, num_filter=0,
               multi_input_mode="concat", workspace=512):
    x = data[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    else:
        b, c, h, w = x.shape
        out = jax.image.resize(x, (b, c, h * scale, w * scale), method="bilinear")
    if len(data) > 1 and multi_input_mode == "concat":
        outs = [out]
        for d in data[1:]:
            s = out.shape[2] // d.shape[2]
            outs.append(jnp.repeat(jnp.repeat(d, s, axis=2), s, axis=3))
        return jnp.concatenate(outs, axis=1)
    return out


@register(name="BilinearResize2D")
def bilinear_resize_2d(data, *, height=0, width=0, scale_height=None,
                       scale_width=None, mode="size", align_corners=True):
    b, c, h, w = data.shape
    if scale_height is not None:
        height, width = int(h * scale_height), int(w * scale_width)
    return jax.image.resize(data, (b, c, height, width), method="bilinear")


@register(name="Moments", aliases=("moments",))
def moments(data, *, axes=None, keepdims=False):
    ax = tuple(axes) if axes is not None else None
    return (jnp.mean(data, axis=ax, keepdims=keepdims),
            jnp.var(data, axis=ax, keepdims=keepdims))


# --------------------------------------------------------------------------
# CTC loss (reference src/operator/nn/ctc_loss.cc / 3rdparty warpctc)
# --------------------------------------------------------------------------

@register(name="CTCLoss", aliases=("ctc_loss",))
def ctc_loss(data, label, data_lengths=None, label_lengths=None, *,
             use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """CTC via optax (jax-native forward-backward; reference uses warp-ctc).
    data: (T, B, C) alphabet incl. blank; label: (B, L)."""
    import optax
    T, B, C = data.shape
    logits = jnp.transpose(data, (1, 0, 2))  # (B, T, C)
    if blank_label == "first":
        # optax expects blank id 0 — matches "first"
        labels = label.astype(jnp.int32)
        blank_id = 0
    else:
        labels = label.astype(jnp.int32)
        blank_id = C - 1
    logit_pad = jnp.zeros((B, T), jnp.float32)
    if use_data_lengths and data_lengths is not None:
        steps = jnp.arange(T)[None, :]
        logit_pad = (steps >= data_lengths.astype(jnp.int32)[:, None]).astype(jnp.float32)
    if use_label_lengths and label_lengths is not None:
        lsteps = jnp.arange(labels.shape[1])[None, :]
        label_pad = (lsteps >= label_lengths.astype(jnp.int32)[:, None]).astype(jnp.float32)
    else:
        label_pad = (labels == (0 if blank_label == "first" else -1)).astype(jnp.float32) * 0
    loss = optax.ctc_loss(logits, logit_pad, labels, label_pad, blank_id=blank_id)
    return loss
