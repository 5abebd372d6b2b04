"""Decayed linear attention in chunks (parallel/linear_attention.py) against
the recurrence it implements, one token a step in float32: the result, the
gradients of q, k and v through the written-out backward pass, the same
result whatever the chunk length, and a length that is no multiple of it."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.parallel.linear_attention import (
    alibi_slopes, lightning_attention, linear_attention_reference)


def _qkv(T, B=2, H=4, D=8, E=8, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k = (jax.random.normal(kk, (B, T, H, D)).astype(dtype)
            for kk in keys[:2])
    v, g = (jax.random.normal(kk, (B, T, H, E)).astype(dtype)
            for kk in keys[2:])
    return q, k, v, g


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
        <= tol * scale


def test_slopes_are_alibis():
    s = np.asarray(alibi_slopes(32))
    assert s.shape == (32,) and s[-1] == pytest.approx(2.0 ** -8)
    assert s[0] == pytest.approx(2.0 ** -0.25)
    assert np.all(np.diff(s) < 0)           # later heads remember longer


@pytest.mark.parametrize("T,chunk", [(48, 16), (48, 8), (50, 16), (50, 64),
                                     (7, 4), (64, 64)])
def test_chunked_matches_the_recurrence(T, chunk):
    q, k, v, _ = _qkv(T)
    slopes = alibi_slopes(4)
    got = lightning_attention(q, k, v, slopes, chunk=chunk)
    assert got.shape == v.shape and got.dtype == q.dtype
    _close(got, linear_attention_reference(q, k, v, slopes), 2e-6)


@pytest.mark.parametrize("T,chunk", [(48, 16), (50, 16), (32, 32)])
def test_the_written_backward_matches_autodiff_of_the_recurrence(T, chunk):
    q, k, v, g = _qkv(T, seed=1)
    slopes = alibi_slopes(4)
    want = jax.grad(lambda *a: jnp.sum(
        linear_attention_reference(*a, slopes) * g), (0, 1, 2))(q, k, v)
    got = jax.grad(lambda *a: jnp.sum(
        lightning_attention(*a, slopes, chunk=chunk) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        _close(a, b, 2e-6)


def test_two_chunk_lengths_agree_and_a_scale_is_a_scale():
    q, k, v, g = _qkv(96, seed=2)
    slopes = alibi_slopes(4)
    a = lightning_attention(q, k, v, slopes, chunk=8)
    b = lightning_attention(q, k, v, slopes, chunk=32)
    _close(a, b, 2e-6)
    c = lightning_attention(q, k, v, slopes, chunk=32, scale=0.25)
    _close(c, 0.25 * b, 1e-6)
    gs = jax.grad(lambda q: jnp.sum(lightning_attention(
        q, k, v, slopes, chunk=32, scale=0.25) * g))(q)
    g1 = jax.grad(lambda q: jnp.sum(lightning_attention(
        q, k, v, slopes, chunk=32) * g))(q)
    _close(gs, 0.25 * g1, 1e-6)


def test_values_of_another_width_and_no_gradient_to_the_decay():
    q, k, v, g = _qkv(40, D=8, E=16, seed=3)
    slopes = alibi_slopes(4)
    _close(lightning_attention(q, k, v, slopes, chunk=16),
           linear_attention_reference(q, k, v, slopes), 2e-6)
    ds = jax.grad(lambda s: jnp.sum(
        lightning_attention(q, k, v, s, chunk=16) * g))(slopes)
    assert not np.any(np.asarray(ds))


def test_it_is_causal_and_a_fast_head_forgets():
    q, k, v, _ = _qkv(32, seed=4)
    slopes = jnp.asarray([0.0, 0.1, 1.0, 50.0])
    a = lightning_attention(q, k, v, slopes, chunk=8)
    v2 = v.at[:, -1].add(1.0)
    k2 = k.at[:, -1].add(1.0)
    b = lightning_attention(q, k2, v2, slopes, chunk=8)
    np.testing.assert_array_equal(np.asarray(a[:, :-1]),
                                  np.asarray(b[:, :-1]))
    # lambda = exp(-50): a token reads itself alone
    own = jnp.einsum("bthd,bthd->bth", q, k)[..., None] * v
    _close(a[:, :, 3], own[:, :, 3], 1e-6)
    # lambda = 1: plain causal linear attention
    full = jnp.einsum("bthd,bshd->bhts", q, k) * jnp.tril(jnp.ones((32, 32)))
    _close(a[:, :, 0], jnp.einsum("bhts,bshe->bthe", full, v)[:, :, 0], 2e-6)


def test_bfloat16_operands_keep_float32_accumulation():
    q, k, v, _ = _qkv(256, D=16, E=16, seed=5, dtype=jnp.bfloat16)
    slopes = alibi_slopes(4)
    got = lightning_attention(q, k, v, slopes, chunk=64)
    assert got.dtype == jnp.bfloat16
    _close(got, linear_attention_reference(q, k, v, slopes), 2e-2)
