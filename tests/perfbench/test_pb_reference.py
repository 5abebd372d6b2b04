"""The two plain float32 references against the program, at a tiny size on
the CPU, in float32 so that the tolerance is rounding and nothing else."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pb_tiny import LM, RESNET
from perfbench import traffic
from perfbench.families import resnet as resnet_family
from perfbench.reference import compare, gpt2, resnet_v1

BOTTLENECK = dict(RESNET, network="resnet50_v1", unit="bottleneck",
                  expansion=4, stage_units=[3, 4, 6, 3])


def _net_and_params(config, training, n=4):
    from incubator_mxnet_tpu.parallel.functional import functionalize
    net, x0 = resnet_family._net(config, seed=7, batch=n)
    params, apply_fn = functionalize(net, [x0], training=training)
    params = dict(params)
    params.update(resnet_family.seeded_norms(params, seed=7))
    x = jnp.asarray(traffic.image_ring(1, n, config["image_shape"], 8)[0])
    got = apply_fn(params, jax.random.PRNGKey(0), x)[0][0]
    return params, x, got


@pytest.mark.parametrize("config", [RESNET, BOTTLENECK],
                         ids=["basic", "bottleneck"])
@pytest.mark.parametrize("training", [True, False],
                         ids=["batch-stats", "running-stats"])
def test_resnet_reference_matches_the_zoo_net(config, training):
    params, x, got = _net_and_params(config, training)
    want = resnet_v1.forward(*resnet_family.reference_args(params), x,
                             config, training=training)
    assert want.shape == got.shape == (4, config["classes"])
    # float32 on both sides: they differ by the order of accumulation only.
    # At 32x32 the last stage normalizes over 4 values a channel, which
    # magnifies that rounding (measured 1e-3; 2.4e-5 at 224x224).
    assert compare(got, want, 5e-3 if training else 1e-4, "")["ok"]


def test_resnet_comparison_has_teeth():
    """Identity BatchNorm leaves (a forward pass that skipped BatchNorm on
    running statistics) must fail by a wide margin."""
    params, x, got = _net_and_params(RESNET, training=False)
    skipped = {k: (jnp.ones_like(v) if k.endswith(("gamma", "running_var"))
                   else jnp.zeros_like(v))
               if k.endswith(("gamma", "beta", "running_mean",
                              "running_var")) else v
               for k, v in params.items()}
    want = resnet_v1.forward(*resnet_family.reference_args(skipped), x,
                             RESNET, training=False)
    assert compare(got, want, 0, "")["relative_error"] > \
        10 * resnet_family.TOLERANCE["bfloat16"]


def test_reference_args_follow_creation_order():
    params, _, _ = _net_and_params(BOTTLENECK, training=False)
    convs, bns, dense = resnet_family.reference_args(params)
    assert len(convs) == len(bns) == 53 and dense[0].shape == (10, 2048)
    assert convs[0].shape == (64, 3, 7, 7)
    assert [c.shape[0] for c in convs[1:5]] == [64, 64, 256, 256]  # + shortcut
    assert all(b[0].shape == (c.shape[0],) for c, b in zip(convs, bns))


def _lm(flash):
    from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                        TransformerLM)
    model = TransformerLM(TransformerConfig(
        vocab_size=LM["vocab_size"], d_model=LM["d_model"],
        n_heads=LM["n_heads"], n_layers=LM["n_layers"], d_ff=LM["d_ff"],
        max_len=LM["max_len"], dtype="float32", remat=True,
        flash_attention=flash))
    params = model.init_params(jax.random.PRNGKey(3))
    # LayerNorm leaves off their identity, so that dropping one would show
    noise = iter(jax.random.split(jax.random.PRNGKey(4), len(params)))
    params = {k: v + 0.1 * jax.random.normal(next(noise), v.shape)
              if "ln" in k else v for k, v in params.items()}
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 48), 0,
                                LM["vocab_size"], jnp.int32)
    return model, params, tokens


@pytest.mark.parametrize("flash", [False, True], ids=["dense", "flash"])
def test_gpt2_reference_matches_transformer_lm(flash):
    model, params, tokens = _lm(flash)
    got = model.apply(params, tokens)
    want = gpt2.forward(params, tokens, LM)
    assert got.shape == want.shape == (2, 48, LM["vocab_size"])
    assert compare(got, want, 1e-4, "")["ok"]


def test_gpt2_reference_is_causal():
    """A later token must not move an earlier position's logits; without
    the mask it moves them by far more than the comparison's tolerance."""
    from perfbench.families import transformer_lm
    _, params, tokens = _lm(False)
    other = tokens.at[:, -1].set((tokens[:, -1] + 1) % LM["vocab_size"])
    a, b = (gpt2.forward(params, t, LM) for t in (tokens, other))
    np.testing.assert_array_equal(np.asarray(a[:, :-1]),
                                  np.asarray(b[:, :-1]))
    assert float(jnp.max(jnp.abs(a[:, -1] - b[:, -1]))) > \
        transformer_lm.TOLERANCE * float(jnp.max(jnp.abs(a)))
