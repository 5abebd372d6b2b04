"""`TransformerLM.loss` is logsumexp less the target's logit, taken from the
head's own logits: against the log-softmax of `TransformerLM.apply`'s
float32 logits, the form it replaced, the loss and every gradient leaf (the
head's, and through the hidden states every layer's) are the same bits in
bfloat16 and the same to 1e-6 in float32; with a width scale on the logits
and through an expert model's routing counts too. `apply` still returns
float32 logits, the float32 image of the head's bfloat16 product."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from incubator_mxnet_tpu.models.transformer import (
    GQA, Experts, TransformerConfig, TransformerLM)

V = 1000


def _log_softmax_form(model, counts=False):
    """The mean next-token loss as it was written before: log-softmax over
    `apply`'s float32 logits, the target's entry gathered from it."""
    def loss(params, tokens, targets):
        logits = model.apply(params, tokens, counts=counts)
        if counts:
            logits, routed = logits
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        if counts:
            return jnp.mean(nll), {k: routed[k]
                                   for k in ("held_slots", "slots_over")}
        return jnp.mean(nll)
    return loss


def _tokens(T=64):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, T), 0, V)
    return tokens, jnp.roll(tokens, -1, 1)


def _same(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == "bfloat16":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6,
                                   atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("logit_scale", [1.0, 1 / 16], ids=["plain", "muP"])
def test_the_loss_and_its_gradients_are_the_log_softmax_forms(dtype,
                                                             logit_scale):
    model = TransformerLM(TransformerConfig(
        vocab_size=V, d_model=128, n_heads=4, n_layers=2, d_ff=256,
        max_len=64, dtype=dtype, logit_scale=logit_scale))
    params = model.init_params(jax.random.PRNGKey(0))
    tokens, targets = _tokens()
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens,
                                                         targets)
    want, want_grads = jax.jit(jax.value_and_grad(_log_softmax_form(model)))(
        params, tokens, targets)
    assert loss.dtype == jnp.float32
    _same(loss, want, dtype)
    assert set(grads) == set(want_grads) == set(params)
    for name in grads:
        assert grads[name].dtype == params[name].dtype, name
        assert float(jnp.max(jnp.abs(want_grads[name]))) > 0, name
        _same(grads[name], want_grads[name], dtype)


def test_an_expert_models_loss_and_counts_are_the_log_softmax_forms():
    model = TransformerLM(TransformerConfig(
        vocab_size=V, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=64,
        dtype="bfloat16", norm="rmsnorm", mlp="swiglu",
        learned_positions=False, tied_head=False, head_dim=16, n_kv_heads=1,
        mixers=("gqa",) * 2, gqa=(GQA(2, None), GQA(2, 16)),
        mlps=("dense", "experts"), flash_attention=False,
        experts=Experts(count=8, held=(2, 4), per_token=2, width=16,
                        shared_width=16, rows=64)))
    params = model.init_params(jax.random.PRNGKey(0))
    tokens, targets = _tokens()
    (loss, routed), grads = jax.jit(jax.value_and_grad(
        lambda p, t, y: model.loss(p, t, y, counts=True), has_aux=True))(
            params, tokens, targets)
    (want, want_routed), want_grads = jax.jit(jax.value_and_grad(
        _log_softmax_form(model, counts=True), has_aux=True))(
            params, tokens, targets)
    _same(loss, want, "bfloat16")
    assert set(routed) == {"held_slots", "slots_over"}
    for k in routed:
        np.testing.assert_array_equal(routed[k], want_routed[k])
    assert int(routed["held_slots"].sum()) > 0
    for name in grads:
        _same(grads[name], want_grads[name], "bfloat16")


def test_apply_returns_the_heads_bfloat16_logits_in_float32():
    model = TransformerLM(TransformerConfig(
        vocab_size=V, d_model=128, n_heads=4, n_layers=2, d_ff=256,
        max_len=64, logit_scale=1 / 16))
    params = model.init_params(jax.random.PRNGKey(0))
    tokens, _ = _tokens()
    logits = jax.jit(model.apply)(params, tokens)
    assert logits.dtype == jnp.float32 and logits.shape == (4, 64, V)
    # every value is a bfloat16 number: the cast that the loss now leaves
    # to its own reductions is exact
    np.testing.assert_array_equal(
        logits, logits.astype(jnp.bfloat16).astype(jnp.float32))
    # and the loss reads the same logits: its value is the log-softmax of
    # these, computed here in numpy in float64
    x = np.asarray(logits, np.float64)
    lse = np.log(np.exp(x - x.max(-1, keepdims=True)).sum(-1)) + x.max(-1)
    targets = np.asarray(jnp.roll(tokens, -1, 1))
    nll = lse - np.take_along_axis(x, targets[..., None], -1)[..., 0]
    assert float(jax.jit(model.loss)(params, tokens, targets)) == \
        pytest.approx(nll.mean(), rel=1e-6)
