"""The arithmetic of the one-pass batch statistics (`ops.nn_ops.
_bn_batch_stats`) that BatchNorm, FusedBNAddReLU and FusedConvBNReLU share:
the shift is a constant of the reduction (`stop_gradient`), so the forward
pass is the shifted formula's bit for bit, and the gradients are those of a
plain two-pass float32 BatchNorm, also where |mean| = 1e3 x std, the case the
shift exists for."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu.ops import nn_ops

C, EPS = 6, 1e-5
OPS = ["BatchNorm", "FusedBNAddReLU", "FusedBNAddReLU+residual",
       "FusedConvBNReLU"]


def _shifted_stats_under_autodiff(data, red):
    """`_bn_batch_stats` as it was with the shift under autodiff."""
    n = int(np.prod([data.shape[i] for i in red]))
    first = lax.slice_in_dim(data, 0, 1, axis=red[0])
    c = jnp.mean(first.astype(jnp.float32), axis=red, keepdims=True)
    shifted = data.astype(jnp.float32) - c
    s1 = jnp.sum(shifted, axis=red, dtype=jnp.float32)
    s2 = jnp.sum(jnp.square(shifted), axis=red, dtype=jnp.float32)
    dmean = s1 / n
    return (jnp.reshape(c, (-1,)) + dmean,
            jnp.maximum(s2 / n - jnp.square(dmean), 0.0))


def _conv1x1(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), [(0, 0), (0, 0)],
        dimension_numbers=nn_ops._conv_dnums(2)).astype(x.dtype)


def _call(op, x, gamma, beta, residual, weight):
    """(out, batch mean, batch var) of the registered op in training
    mode."""
    moving = (jnp.zeros((C,), jnp.float32), jnp.ones((C,), jnp.float32))
    kw = {"eps": EPS, "fix_gamma": False, "training": True}
    if op == "BatchNorm":
        return nn_ops.batch_norm.fn(x, gamma, beta, *moving, **kw)
    if op.startswith("FusedBNAddReLU"):
        return nn_ops.fused_bn_add_relu.fn(
            x, gamma, beta, *moving,
            residual if op.endswith("residual") else None, **kw)
    return nn_ops.fused_conv_bn_relu.fn(
        x, weight, gamma, beta, *moving, kernel=(1, 1), num_filter=C, **kw)


def _system(op):
    return lambda *args: _call(op, *args)[0]


def _two_pass(op):
    """The same layer on float32 `jnp.mean` / `jnp.var` statistics; the
    output is rounded to the data's dtype before the residual and the ReLU,
    as the ops do."""
    def f(x, gamma, beta, residual, weight):
        z = _conv1x1(x, weight) if op == "FusedConvBNReLU" else x
        z32 = z.astype(jnp.float32)
        mean = jnp.mean(z32, axis=(0, 2, 3), keepdims=True)
        var = jnp.var(z32, axis=(0, 2, 3), keepdims=True)
        scale = jnp.reshape(gamma, (1, -1, 1, 1)) * lax.rsqrt(var + EPS)
        out = (z32 * scale + (jnp.reshape(beta, (1, -1, 1, 1))
                              - mean * scale)).astype(z.dtype)
        if op == "BatchNorm":
            return out
        if op.endswith("residual"):
            out = out + residual
        return jnp.maximum(out, 0)
    return f


def _inputs(dtype, centre):
    """Activations of std about 1 round `centre` in every channel (the
    convolution's weight keeps the centre: positive, summing to about 1)."""
    rng = np.random.RandomState(7)
    x = (centre + rng.randn(8, C, 5, 5)).astype(np.float32)
    gamma = rng.uniform(0.5, 1.5, C).astype(np.float32)
    beta = (0.1 * rng.randn(C)).astype(np.float32)
    residual = rng.randn(8, C, 5, 5).astype(np.float32)
    weight = (np.eye(C) * 0.7 + 0.3 / C
              + 0.02 * rng.randn(C, C)).astype(np.float32)[:, :, None, None]
    probe = rng.randn(8, C, 5, 5).astype(np.float32)
    return ([jnp.asarray(x, dtype), jnp.asarray(gamma), jnp.asarray(beta),
             jnp.asarray(residual, dtype), jnp.asarray(weight, dtype)],
            jnp.asarray(probe))


def _loss(f, probe):
    return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * probe)


# Largest |got - want| over the largest |want|, three to six times what the
# shifted formula reads here with or without the shift under autodiff (the two
# agree to a digit). float32 round 0: rounding of 200-element sums. float32
# round 1e3: gamma's gradient is a difference of sums 1e3 times its size, in
# the reference's multiply-add epilogue as in the ops'; unshifted one-pass
# statistics would miss the variance, and these gradients, by percents.
# bfloat16: the activation's gradient is rounded to 8 bits.
TOL = {("float32", 0.0): 2e-6, ("float32", 1e3): 2e-3,
       ("bfloat16", 0.0): 2e-2, ("bfloat16", 1e3): 2e-2}


@pytest.mark.parametrize("centre", [0.0, 1e3])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("op", OPS)
def test_forward_is_the_shifted_formula_bit_for_bit(monkeypatch, op, dtype,
                                                    centre):
    args, _ = _inputs(dtype, centre)
    got = jax.jit(_system(op))(*args)
    monkeypatch.setattr(nn_ops, "_bn_batch_stats",
                        _shifted_stats_under_autodiff)
    want = jax.jit(_system(op))(*args)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("centre", [0.0, 1e3])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("op", OPS)
def test_gradients_are_the_two_pass_references(op, dtype, centre):
    """With respect to data, gamma and beta, and the residual where the op
    has one."""
    args, probe = _inputs(dtype, centre)
    wrt = (0, 1, 2) + ((3,) if op.endswith("residual") else ())
    got = jax.jit(jax.grad(_loss(_system(op), probe), wrt))(*args)
    want = jax.jit(jax.grad(_loss(_two_pass(op), probe), wrt))(*args)
    for i, g, w in zip(wrt, got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert g.shape == w.shape and np.all(np.isfinite(g))
        err = np.abs(g - w).max() / np.abs(w).max()
        assert err <= TOL[dtype, centre], (i, err)


def test_the_shift_takes_no_gradient():
    """Nothing of the slice the shift is read from reaches the backward
    pass: no `pad` puts a cotangent back into the batch."""
    x = jnp.ones((4, C, 3, 3))

    def f(x_):
        mean, var = nn_ops._bn_batch_stats(x_, (0, 2, 3))
        return jnp.sum(mean) + jnp.sum(var)
    assert "pad" not in str(jax.make_jaxpr(jax.grad(f))(x))


@pytest.mark.parametrize("op", OPS)
def test_zero_size_batch_gives_nan_statistics_and_no_error(op):
    x = jnp.zeros((0, C, 2, 2))
    out, mean, var = _call(op, x, jnp.ones((C,)), jnp.zeros((C,)), x,
                           jnp.ones((C, C, 1, 1)))
    assert out.shape == x.shape and mean.shape == var.shape == (C,)
    assert np.all(np.isnan(np.asarray(mean)))
    assert np.all(np.isnan(np.asarray(var)))


@pytest.mark.parametrize("hybridize", [False, True])
def test_second_derivative_through_a_batchnorm_block(hybridize):
    """A gradient penalty through Conv2D + BatchNorm + ReLU, eager and
    hybridized, against jax's own second derivative of the two-pass
    layer."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(C, 1, use_bias=False, in_channels=C),
            gluon.nn.BatchNorm(in_channels=C), gluon.nn.Activation("relu"))
    net.initialize(mx.init.Xavier())
    if hybridize:
        net.hybridize()
    args, _ = _inputs("float32", 0.0)
    x = nd.array(np.asarray(args[0]))
    x.attach_grad()
    with autograd.record():
        y = net(x)
        g = autograd.grad((y * y).sum(), x, create_graph=True,
                          retain_graph=True)
        penalty = (g * g).sum()
    penalty.backward()

    weight = jnp.asarray(net[0].weight.data().asnumpy())
    gamma = jnp.asarray(net[1].gamma.data().asnumpy())
    beta = jnp.asarray(net[1].beta.data().asnumpy())

    def layer(x_):
        return _two_pass("FusedConvBNReLU")(x_, gamma, beta, None, weight)
    first = jax.grad(lambda x_: jnp.sum(jnp.square(layer(x_))))
    want = jax.grad(lambda x_: jnp.sum(jnp.square(first(x_))))(args[0])
    np.testing.assert_allclose(x.grad.asnumpy(), np.asarray(want),
                               rtol=2e-3, atol=2e-4)
