"""`TransformerLM` with a layer list (one "sparse" layer and three
"lightning" layers: the MiniCPM-SALA pattern) against the plain float32
reference in perfbench/reference/minicpm_sala.py, at tiny widths with a tiny
`dense_len`, block, top-k and window so that block selection runs: logits,
loss and every gradient leaf, below and above `dense_len`. Grouped K/V heads
through the flash kernels, the selection's own rules, and GPT-2's
configuration on its old path bit for bit."""
import copy
import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                    TransformerLM)
from incubator_mxnet_tpu.parallel.flash_attention import flash_attention
from incubator_mxnet_tpu.parallel.ring_attention import attention_reference
from incubator_mxnet_tpu.parallel.sparse_attention import (
    BlockSelect, block_sparse_attention, select_blocks)
from perfbench import cells
from perfbench.families import minicpm_sala as family
from perfbench.reference import minicpm_sala as reference

SELECT = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=2,
              init_blocks=1, window_size=8, dense_len=32)


def _tiny():
    """The cell's configuration with every width made tiny (a test's right,
    never a cell's) and the selection shrunk so that 48 tokens select."""
    config = copy.deepcopy(cells.resolve("minicpm-sala.train-8k").config)
    config.update(hidden_size=32, intermediate_size=64, head_dim=8,
                  lightning_head_dim=8, num_attention_heads=4, lightning_nh=4,
                  lightning_nkv=4, num_key_value_heads=2, vocab_size=96,
                  dim_model_base=8, sparse=SELECT)
    return config


def _model(config, dtype="float32", remat=True):
    model = TransformerLM(family.model_config(
        config, dict(dtype=dtype, remat=remat)))
    params = model.init_params(jax.random.PRNGKey(0))
    noise = iter(jax.random.split(jax.random.PRNGKey(9), len(params)))
    # norm weights off their identity, so that dropping one would show
    params = {k: v + 0.1 * jax.random.normal(next(noise), v.shape)
              if v.ndim == 1 else v for k, v in sorted(params.items())}
    return model, params


def _worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("T,remat", [(32, True), (48, True), (61, False)],
                         ids=["dense", "selected", "selected-ragged-plain"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(T, remat):
    config = _tiny()
    model, params = _model(config, remat=remat)
    assert model.mixers == ("sparse", "lightning", "lightning", "lightning")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, 96)
    targets = jnp.roll(tokens, -1, 1)
    got = jax.jit(model.apply)(params, tokens)
    want = reference.forward(params, tokens, config)
    assert got.shape == want.shape == (2, T, 96)
    assert _worst(got, want) < 2e-6
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens,
                                                         targets)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, targets, config)))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert set(grads) == set(ref_grads) == set(params)
    for name in grads:
        assert float(jnp.max(jnp.abs(ref_grads[name]))) > 0, name
        assert _worst(grads[name], ref_grads[name]) < 2e-5, name


@pytest.mark.parametrize("drop", ["decay", "rope", "precision"])
def test_each_control_moves_the_reference_by_far_more_than_rounding(drop):
    config = _tiny()
    _, params = _model(config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 96)
    want = reference.forward(params, tokens, config)
    off = reference.forward(params, tokens, config, drop=(drop,))
    assert _worst(off, want) > 5e-3


def test_the_configuration_is_1_184_6_million_parameters():
    config = cells.resolve("minicpm-sala.train-8k").config
    model = TransformerLM(family.model_config(
        config, dict(dtype="bfloat16", remat=True)))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    total = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert total == config["parameters"] == 1_184_654_080
    assert shapes["head"].shape == shapes["embed"].shape == (9181, 4096)
    assert shapes["layer0_wk"].shape == (4096, 256)
    assert shapes["layer1_wk"].shape == (4096, 4096)
    assert "pos_embed" not in shapes and "layer0_q_norm_g" not in shapes
    assert model._param_names() == [n for n, _, _ in model._shapes()]
    assert set(model._param_names()) == set(shapes)


def test_a_layer_list_is_checked_and_has_no_sp_or_tp_path_yet():
    with pytest.raises(ValueError, match="mixers"):
        TransformerLM(TransformerConfig(n_layers=2, mixers=("mha",)))
    with pytest.raises(ValueError, match="mixers"):
        TransformerLM(TransformerConfig(n_layers=1, mixers=("mamba",)))
    model, params = _model(_tiny())
    x = jnp.zeros((1, 16, 32))
    with pytest.raises(NotImplementedError, match="lightning"):
        model._block(params, "layer1_", x, "sp")


# -- grouped K/V heads through the flash kernels --------------------------------

@pytest.mark.parametrize("heads,kv,d", [(4, 2, 64), (4, 1, 128), (2, 2, 64)])
def test_grouped_kv_through_flash_attention(heads, kv, d):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 128, heads, d), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, 128, kv, d), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(rng.randn(2, 128, heads, d), jnp.float32)
    wide = lambda x: jnp.repeat(x, heads // kv, axis=2)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, wide(k), wide(v), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True) * g),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(attention_reference(
        q, wide(k), wide(v), causal=True) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-4)


# -- the selection's rules ----------------------------------------------------------

def test_selection_keeps_first_local_and_topk_blocks_and_is_causal():
    sel = BlockSelect(kernel=4, stride=2, block=8, topk=2, init_blocks=1,
                      window=8, dense_len=32)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 80, 4, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 80, 2, 8), jnp.float32)
    chosen = np.asarray(jax.jit(lambda q, k: select_blocks(q, k, sel))(q, k))
    assert chosen.shape == (2, 2, 80, 10)
    t = np.arange(80)
    own = t // 8
    assert not chosen[..., np.arange(10)[None, :] > own[:, None]].any()
    assert chosen[:, :, :, 0].all()                     # the first block
    assert chosen[:, :, t, own].all()                   # its own block
    assert chosen[:, :, t[8:], own[8:] - 1][..., t[8:] % 8 < 7].all()  # window
    count = chosen.sum(-1)
    assert count.max() == 1 + 2 + 2     # first, two local, two best others
    assert (count[:, :, 40:] >= 1 + 1 + 2).all()
    # the mask of the reference is the same choice, token by token
    mask = np.asarray(jax.jit(
        lambda q, k: reference._selection_mask(q, k, SELECT))(q, k))
    want = np.repeat(chosen, 8, -1) & (t[None, :] <= t[:, None])
    np.testing.assert_array_equal(mask, want)


def test_with_every_block_selected_it_is_plain_grouped_attention():
    sel = BlockSelect(kernel=4, stride=2, block=8, topk=64, init_blocks=1,
                      window=8, dense_len=0)
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 40, 4, 8), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, 40, 2, 8), jnp.float32)
            for _ in range(2))
    wide = lambda x: jnp.repeat(x, 2, axis=2)
    np.testing.assert_allclose(
        np.asarray(jax.jit(
            lambda *a: block_sparse_attention(*a, sel))(q, k, v)),
        np.asarray(attention_reference(q, wide(k), wide(v), causal=True)),
        rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="multiples of stride"):
        BlockSelect(kernel=5, stride=2)


# -- GPT-2's path is where it was -----------------------------------------------------
# sha1 of the parameters (as float32) and of the logits' bytes, computed by
# the parent commit of the PR that gave TransformerConfig a layer list

OLD = {("bfloat16", False): ("d32ffb90094e0f33", "8d1ce3992dae50d7"),
       ("bfloat16", True): ("d32ffb90094e0f33", "b2e5c40ebeae85da"),
       ("float32", False): ("82bf6dd4f6866ac7", "6a1a397f5bd4150d"),
       ("float32", True): ("82bf6dd4f6866ac7", "c1e8e5385933c66b")}


@pytest.mark.parametrize("dtype,flash", sorted(OLD))
def test_gpt2s_configuration_gives_its_old_logits_bit_for_bit(dtype, flash):
    model = TransformerLM(TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_len=64, dtype=dtype, remat=True, flash_attention=flash))
    params = model.init_params(jax.random.PRNGKey(3))
    assert sorted(params) == sorted(
        ["embed", "pos_embed", "lnf_g", "lnf_b"] + [
            f"layer{i}_{s}" for i in range(2) for s in (
                "ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "ln2_g", "ln2_b",
                "w_in", "w_out")])
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 48), 0, 128,
                                jnp.int32)
    logits = np.asarray(model.apply(params, tokens))
    sha = lambda b: hashlib.sha1(b).hexdigest()[:16]
    assert (sha(b"".join(np.asarray(params[k].astype(jnp.float32)).tobytes()
                         for k in sorted(params))),
            sha(logits.tobytes())) == OLD[(dtype, flash)]
