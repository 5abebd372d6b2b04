"""`TransformerLM` with Laguna-S-2.1's layer list (a dense leading layer and
a period of three windowed 6-head layers and one full 4-head layer over 2
K/V heads, YaRN on the full ones, a gate a head, routed experts of which
this chip holds a range, a shared expert) against the plain float32
reference in perfbench/reference/laguna.py, at tiny widths: logits, loss and
every gradient leaf; YaRN's frequencies against values computed by hand; the
controls; the cut's parameter count."""
import copy
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from incubator_mxnet_tpu.models.transformer import (
    GQA, Experts, Rotary, TransformerConfig, TransformerLM, yarn_inv_freq)
from incubator_mxnet_tpu.parallel import make_mesh
from perfbench import cells
from perfbench.families import laguna as family
from perfbench.reference import laguna as reference

CELL = "laguna-s-2.1.train-8k"


def _tiny(held=(4, 4)):
    """The cell's configuration with every width made tiny (a test's right,
    never a cell's): 16 experts of which 4 are held, 3 a token, a window of
    16, heads of 16 lanes."""
    config = copy.deepcopy(cells.resolve(CELL).config)
    heads = [4 if h == 48 else 6
             for h in config["num_attention_heads_per_layer"]]
    config.update(hidden_size=32, intermediate_size=64, head_dim=16,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_attention_heads_per_layer=heads, sliding_window=16,
                  moe_intermediate_size=16,
                  shared_expert_intermediate_size=16, num_experts_per_tok=3,
                  num_experts=held[1], vocab_size=96,
                  experts_held={"first": held[0], "count": held[1]})
    config["published"] = dict(config["published"], num_experts=16)
    return config


def _model(config, dtype="float32", remat=True, rows=0):
    model = TransformerLM(family.model_config(
        config, dict(dtype=dtype, remat=remat, expert_rows=rows)))
    params = model.init_params(jax.random.PRNGKey(0))
    noise = iter(jax.random.split(jax.random.PRNGKey(9), len(params)))
    params = {k: v + 0.1 * jax.random.normal(next(noise), v.shape)
              if v.ndim == 1 else v for k, v in sorted(params.items())}
    return model, params


def _worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("T,remat", [(48, True), (40, False)],
                         ids=["remat", "plain"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(T, remat):
    config = _tiny()
    model, params = _model(config, remat=remat)
    assert model.mixers == ("gqa",) * 5
    assert model.mlps == ("dense",) + ("experts",) * 4
    assert [g.heads for g in model.cfg.gqa] == [4, 6, 6, 6, 4]
    assert [g.window for g in model.cfg.gqa] == [None, 16, 16, 16, None]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, 96)
    targets = jnp.roll(tokens, -1, 1)
    got, counts = jax.jit(lambda p, t: model.apply(p, t, counts=True))(
        params, tokens)
    want, chosen = reference.forward(params, tokens, config,
                                     with_choices=True)
    assert got.shape == want.shape == (2, T, 96)
    assert _worst(got, want) < 5e-6
    # the step's own counts are the reference's choices that fall here
    assert sorted(chosen) == [1, 2, 3, 4]
    held = [int(jnp.sum((chosen[i] >= 4) & (chosen[i] < 8)))
            for i in sorted(chosen)]
    assert counts["held_slots"].tolist() == held
    assert counts["slots_over"].tolist() == [0] * 4
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens,
                                                         targets)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(p, tokens, targets, config))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert set(grads) == set(ref_grads) == set(params)
    for name in grads:
        assert float(jnp.max(jnp.abs(ref_grads[name]))) > 0, name
        assert _worst(grads[name], ref_grads[name]) < 5e-5, name


def test_the_reference_takes_choices_handed_to_it():
    config = _tiny()
    _, params = _model(config)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, 96)
    want, chosen = reference.forward(params, tokens, config,
                                     with_choices=True)
    same = reference.forward(params, tokens, config, choices=chosen)
    assert _worst(same, want) < 1e-6
    other = {i: (c + 1) % 16 for i, c in chosen.items()}
    assert _worst(reference.forward(params, tokens, config, choices=other),
                  want) > 1e-3


@pytest.mark.parametrize("drop", ["window", "gate", "experts", "yarn",
                                  "precision"])
def test_each_control_moves_the_reference_by_far_more_than_rounding(drop):
    config = _tiny()
    _, params = _model(config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 96)
    want = reference.forward(params, tokens, config)
    off = reference.forward(params, tokens, config, drop=(drop,))
    assert _worst(off, want) > 5e-3


def test_yarn_frequencies_against_values_computed_by_hand():
    """The published full layers: 64 rotated lanes of 128, theta 500,000,
    factor 128, original 8,192, beta 32 and 1. A lane turns beta times over
    8,192 positions at i = 32 ln(8192 / (2 pi beta)) / ln 500000: 9.04 for
    32 (floor 9), 17.49 for 1 (ceiling 18). So pairs 0-9 keep theta^(-i/32),
    pairs 18-31 are that over 128, and pair 12 is a third of the way."""
    rope = cells.resolve(CELL).config["rope_parameters"]["full_attention"]
    freq = yarn_inv_freq(64, 500000.0, 128, 8192, 32, 1)
    assert freq.shape == (32,)
    plain = lambda i: 500000.0 ** (-i / 32)
    assert math.floor(32 * math.log(8192 / (64 * math.pi))
                      / math.log(5e5)) == 9
    assert math.ceil(32 * math.log(8192 / (2 * math.pi))
                     / math.log(5e5)) == 18
    for i in (0, 5, 9):
        assert freq[i] == pytest.approx(plain(i), rel=1e-12)
    for i in (18, 25, 31):
        assert freq[i] == pytest.approx(plain(i) / 128, rel=1e-12)
    assert freq[12] == pytest.approx(plain(12) * (2 / 3 + 1 / 3 / 128),
                                     rel=1e-12)
    assert freq[1] == pytest.approx(0.6636, rel=1e-3)        # 5e5^(-1/32)
    assert freq[31] == pytest.approx(2.3545e-8, rel=1e-3)   # 5e5^(-31/32)/128
    # the reference's own, written apart from the program's, agrees
    mine, r, factor = reference.inv_freq(rope, 128)
    assert (r, factor) == (64, rope["attention_factor"])
    np.testing.assert_allclose(np.asarray(mine), freq, rtol=2e-6)
    plain_ref, r, factor = reference.inv_freq(
        cells.resolve(CELL).config["rope_parameters"]["sliding_attention"],
        128)
    assert (r, factor) == (128, 1.0)
    np.testing.assert_allclose(np.asarray(plain_ref),
                               10000.0 ** (-np.arange(64) / 64), rtol=2e-6)
    assert rope["attention_factor"] == pytest.approx(
        0.1 * math.log(128) + 1, rel=1e-9)


def test_the_configuration_is_811_0_million_parameters():
    config = cells.resolve(CELL).config
    model = TransformerLM(family.model_config(
        config, dict(dtype="bfloat16", remat=True, expert_rows=4096)))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    total = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert total == config["parameters"] == 811_017_216
    routed = sum(int(np.prod(v.shape)) for k, v in shapes.items()
                 if "_e_" in k)
    assert routed == 301_989_888
    assert shapes["head"].shape == shapes["embed"].shape == (12544, 3072)
    assert shapes["layer0_wq"].shape == (3072, 48 * 128)
    assert shapes["layer1_wq"].shape == (3072, 72 * 128)
    assert shapes["layer1_wk"].shape == (3072, 8 * 128)
    assert shapes["layer1_wg"].shape == (3072, 72)
    assert shapes["layer0_w_in"].shape == (3072, 12288)
    assert shapes["layer1_router"].shape == (3072, 256)
    assert shapes["layer4_e_gate_in"].shape == (8, 3072, 2048)
    assert shapes["layer4_s_out"].shape == (1024, 3072)
    assert "layer0_router" not in shapes and "layer1_w_in" not in shapes
    assert model.cfg.experts.rows == 4096 and model.cfg.experts.held == (0, 8)
    assert set(model._param_names()) == set(shapes)


def test_the_old_seeds_draw_the_old_weights():
    """init_params spends the split it always spent: a model with no more
    matrices than it holds draws what it drew (GPT-2's first matrix by its
    sum, recorded before the layer list grew)."""
    model = TransformerLM(TransformerConfig(vocab_size=64, d_model=32,
                                            n_heads=4, n_layers=2, d_ff=64,
                                            max_len=16, dtype="float32"))
    params = model.init_params(jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(0), 4 + 8 * 2)
    np.testing.assert_array_equal(
        np.asarray(params["embed"]),
        np.asarray(jax.random.normal(keys[0], (64, 32)) / math.sqrt(32)))
    np.testing.assert_array_equal(
        np.asarray(params["layer1_w_out"]),
        np.asarray(jax.random.normal(keys[13], (64, 32)) / math.sqrt(64)))


def test_a_layer_list_with_experts_is_checked_and_has_no_sp_or_tp_path():
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
                max_len=16, dtype="float32", norm="rmsnorm", mlp="swiglu",
                learned_positions=False, tied_head=False, head_dim=16,
                n_kv_heads=1, mixers=("gqa",), gqa=(GQA(2, 8),))
    with pytest.raises(ValueError, match="mlps"):
        TransformerLM(TransformerConfig(**base, mlps=("sparse",)))
    with pytest.raises(ValueError, match="wants `experts`"):
        TransformerLM(TransformerConfig(**base, mlps=("experts",)))
    with pytest.raises(ValueError, match="wants its GQA"):
        TransformerLM(TransformerConfig(**dict(base, gqa=())))
    ex = Experts(count=8, held=(0, 4), per_token=2, width=16, shared_width=16)
    model = TransformerLM(TransformerConfig(**base, mlps=("experts",),
                                            experts=ex))
    params = model.init_params(jax.random.PRNGKey(0))
    x = jnp.zeros((1, 8, 32))
    with pytest.raises(NotImplementedError, match="gqa"):
        model._block(params, "layer0_", x, "sp")
    # an "mha" layer goes through shard_map; its expert MLP does not
    dense = TransformerLM(TransformerConfig(**dict(
        base, mixers=(), gqa=(), head_dim=None, flash_attention=False),
        mlps=("experts",), experts=ex))
    from incubator_mxnet_tpu.parallel._compat import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh({"sp": 1}, jax.devices()[:1])
    with pytest.raises(NotImplementedError, match="experts"):
        shard_map(lambda p, y: dense._block(p, "layer0_", y, "sp"), mesh,
                  (P(), P()), P())(dense.init_params(jax.random.PRNGKey(0)),
                                   x)


def test_the_step_hands_back_its_routing_counts_beside_the_loss():
    config = _tiny()
    model, params = _model(config, rows=64)
    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    step, shard, init = model.make_train_step(mesh, lr=1e-3, use_sp=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 0, 96)
    state = shard(params)
    opt = init(state)
    losses = []
    for i in range(3):
        state, opt, loss, routed = step(state, opt, tokens,
                                        jnp.roll(tokens, -1, 1), i)
        losses.append(float(loss))
        assert routed["held_slots"].shape == routed["slots_over"].shape == \
            (4,)
        assert int(routed["held_slots"].sum()) > 0
        assert routed["slots_over"].tolist() == [0] * 4
    assert losses[2] < losses[0]
    # a dense model's step is the three it always returned
    plain = TransformerLM(TransformerConfig(vocab_size=64, d_model=32,
                                            n_heads=4, n_layers=1, d_ff=64,
                                            max_len=16, dtype="float32"))
    step, shard, init = plain.make_train_step(mesh, use_sp=False)
    state = shard(plain.init_params(jax.random.PRNGKey(0)))
    assert len(step(state, init(state), tokens[:, :16] % 64,
                    tokens[:, :16] % 64, 0)) == 3
