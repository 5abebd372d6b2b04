"""`TransformerLM` with LFM2-8B-A1B's layer list (two leading conv layers
with a dense SwiGLU, then a period of one QK-normed, ungated GQA layer and
three conv layers over routed experts of which this chip holds a range,
selected by an expert bias that the step balances) against the plain float32
reference in perfbench/reference/lfm2_moe.py, at tiny widths: the short
convolution against a loop, forward and backward, and its causality; logits,
loss and every gradient leaf; QK-norm and the output gate switched; the
biased router and its balancing rule; the held experts' shares against the
uncut layer; GPT-2's logits with the defaults; the cut's parameter count."""
import copy
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from incubator_mxnet_tpu.models.transformer import (
    GQA, Rotary, TransformerConfig, TransformerLM, short_conv)
from incubator_mxnet_tpu.parallel import make_mesh
from incubator_mxnet_tpu.parallel.moe import (moe_balance, moe_dispatch,
                                              moe_route)
from perfbench import cells
from perfbench.families import lfm2_moe as family
from perfbench.reference import lfm2_moe as reference

CELL = "lfm2-8b-a1b.train-4x8k-moe"


def _tiny(held=(4, 4)):
    """The cell's configuration with every width made tiny (a test's right,
    never a cell's): 16 experts of which 4 are held, 3 a token, heads of 8
    lanes."""
    config = copy.deepcopy(cells.resolve(CELL).config)
    config.update(hidden_size=32, intermediate_size=64, num_attention_heads=4,
                  num_key_value_heads=2, moe_intermediate_size=16,
                  num_experts_per_tok=3, num_experts=held[1], vocab_size=96,
                  experts_held={"first": held[0], "count": held[1]})
    config["published"] = dict(config["published"], num_experts=16)
    return config


def _model(config, dtype="float32", remat=True, rows=0):
    model = TransformerLM(family.model_config(
        config, dict(dtype=dtype, remat=remat, expert_rows=rows)))
    return model, family.draw_params(model, jax.random.PRNGKey(0))


def _worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _loop_conv(u, w):
    """v[b, t] = sum over j of w[j] u[b, t - 2 + j], written as a loop over
    positions, zero before the first."""
    B, T, C = u.shape
    v = np.zeros((B, T, C))
    for t in range(T):
        for j in range(w.shape[0]):
            s = t - (w.shape[0] - 1) + j
            if s >= 0:
                v[:, t] += w[j] * u[:, s]
    return v


def test_the_short_convolution_against_a_loop_forward_and_backward():
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 5))
    want = _loop_conv(np.asarray(u, np.float64), np.asarray(w, np.float64))
    np.testing.assert_allclose(np.asarray(short_conv(u, w)), want, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(reference.conv_taps(u[0], w)), want[0], rtol=1e-5,
        atol=1e-5)
    # every gradient: the loop's adjoint, by a cotangent g
    g = jax.random.normal(jax.random.PRNGKey(2), u.shape)
    du, dw = jax.grad(lambda a, b: jnp.sum(short_conv(a, b) * g),
                      argnums=(0, 1))(u, w)
    gn, un = np.asarray(g, np.float64), np.asarray(u, np.float64)
    want_du = np.zeros(u.shape)
    want_dw = np.zeros(w.shape)
    for t in range(u.shape[1]):
        for j in range(3):
            s = t - 2 + j
            if s >= 0:
                want_du[:, s] += np.asarray(w[j]) * gn[:, t]
                want_dw[j] += np.sum(un[:, s] * gn[:, t], 0)
    np.testing.assert_allclose(np.asarray(du), want_du, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), want_dw, rtol=1e-5, atol=1e-5)
    # bfloat16 in, bfloat16 out, the taps summed in float32
    assert short_conv(u.astype(jnp.bfloat16), w).dtype == jnp.bfloat16


def test_a_change_at_t_never_moves_an_output_before_t():
    config = _tiny()
    model, params = _model(config)
    assert model.mixers[:2] == ("conv", "conv")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 96)
    got = model.apply(params, tokens)
    for t in (0, 7, 23):
        moved = model.apply(params, tokens.at[0, t].set(
            (tokens[0, t] + 1) % 96))
        np.testing.assert_array_equal(np.asarray(moved[0, :t]),
                                      np.asarray(got[0, :t]))
        assert float(jnp.max(jnp.abs(moved[0, t] - got[0, t]))) > 1e-3
    # and the conv mixer alone, on a hidden state
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 32))
    y = model._conv(params, "layer0_", h)
    y2 = model._conv(params, "layer0_", h.at[0, 9].add(1.0))
    assert float(jnp.max(jnp.abs(y2[0, :9] - y[0, :9]))) == 0.0
    assert float(jnp.max(jnp.abs(y2[0, 9:12] - y[0, 9:12]))) > 1e-3
    np.testing.assert_array_equal(np.asarray(y2[0, 12:]),
                                  np.asarray(y[0, 12:]))      # 3 taps


@pytest.mark.parametrize("T,remat", [(48, True), (40, False)],
                         ids=["remat", "plain"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(T, remat):
    config = _tiny()
    model, params = _model(config, remat=remat)
    assert model.mixers == ("conv", "conv", "gqa", "conv", "conv", "conv")
    assert model.mlps == ("dense",) * 2 + ("experts",) * 4
    assert model.cfg.gqa[2] == GQA(4, rotary=Rotary(theta=1e6),
                                   qk_norm=True, gate=False)
    assert "layer2_wg" not in params and "layer2_q_norm_g" in params
    assert params["layer2_e_bias"].dtype == jnp.float32
    assert float(jnp.std(params["layer2_e_bias"])) > 0.05   # drawn
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, 96)
    targets = jnp.roll(tokens, -1, 1)
    got, counts = jax.jit(lambda p, t: model.apply(p, t, counts=True))(
        params, tokens)
    want, chosen = reference.forward(params, tokens, config,
                                     with_choices=True)
    assert got.shape == want.shape == (2, T, 96)
    assert _worst(got, want) < 5e-6
    assert sorted(chosen) == [2, 3, 4, 5]
    held = [int(jnp.sum((chosen[i] >= 4) & (chosen[i] < 8)))
            for i in sorted(chosen)]
    assert counts["held_slots"].tolist() == held
    assert counts["slots_over"].tolist() == [0] * 4
    load = [np.bincount(np.asarray(chosen[i]).reshape(-1), minlength=16)
            for i in sorted(chosen)]
    np.testing.assert_array_equal(np.asarray(counts["load"]), load)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens,
                                                         targets)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(p, tokens, targets, config))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert set(grads) == set(ref_grads) == set(params)
    for name in grads:
        if name.endswith("_e_bias"):            # selects, never weights
            assert float(jnp.max(jnp.abs(grads[name]))) == 0.0, name
            continue
        assert float(jnp.max(jnp.abs(ref_grads[name]))) > 0, name
        assert _worst(grads[name], ref_grads[name]) < 5e-5, name


@pytest.mark.parametrize("qk_norm,gate", [(True, False), (False, False),
                                          (True, True), (False, True)])
def test_qk_norm_and_the_gate_switched(qk_norm, gate):
    """A "gqa" layer with QK-norm on or off and its gate kept or dropped:
    the program against a plain computation of its own attention."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        max_len=64, dtype="float32", norm="rmsnorm", mlp="swiglu",
        learned_positions=False, tied_head=False, head_dim=8, n_kv_heads=2,
        mixers=("gqa",), gqa=(GQA(4, qk_norm=qk_norm, gate=gate),),
        flash_attention=False)
    model = TransformerLM(cfg)
    params = family.draw_params(model, jax.random.PRNGKey(0))
    assert ("layer0_q_norm_g" in params) == qk_norm
    assert ("layer0_wg" in params) == gate
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 32))
    got = model._attn(params, "layer0_", x, None, None, None)
    rms = lambda a, g: a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True)
                                    + 1e-5) * g
    h = rms(x, params["layer0_ln1_g"])
    q, k, v = ((h @ params["layer0_" + w]).reshape(1, 16, -1, 8)
               for w in ("wq", "wk", "wv"))
    if qk_norm:
        q, k = rms(q, params["layer0_q_norm_g"]), \
            rms(k, params["layer0_k_norm_g"])
    q, k = (reference._rotary(a[0], 10000.0)[None] for a in (q, k))
    k, v = (jnp.repeat(a, 2, axis=2) for a in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(8)
    s = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), s, -jnp.inf)
    a = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    if gate:
        a = a * jax.nn.sigmoid(h @ params["layer0_wg"])[..., None]
    want = a.reshape(1, 16, 32) @ params["layer0_wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_the_router_selects_by_s_plus_b_and_weights_by_s():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8)) / 4
    s = np.asarray(jax.nn.sigmoid(x @ router))
    bias = jnp.zeros((8,)).at[5].set(2.0).at[1].set(-2.0)
    experts, weights = moe_route(x, router, 3, bias=bias)
    experts, weights = np.asarray(experts), np.asarray(weights)
    assert (experts == 5).any(1).all()          # +2 puts 5 in every row
    assert not (experts == 1).any()             # -2 keeps 1 out
    by = s + np.asarray(bias)
    for n in range(64):
        assert set(experts[n]) == set(np.argsort(-by[n])[:3])
        picked = s[n, experts[n]]
        np.testing.assert_allclose(weights[n], picked / picked.sum(),
                                   rtol=1e-6)
    # no bias: the k largest of s, as before
    plain, _ = moe_route(x, router, 3)
    assert [set(r) for r in np.asarray(plain)] == \
        [set(np.argsort(-s[n])[:3]) for n in range(64)]
    # a bias of zeros chooses and weighs what no bias does
    same = moe_route(x, router, 3, bias=jnp.zeros((8,)))
    np.testing.assert_array_equal(np.asarray(same[0]), np.asarray(plain))


def test_the_balancing_rule_moves_the_bias_by_its_rate_towards_the_mean():
    bias = jnp.asarray([0.5, -0.25, 0.0, 0.125], jnp.float32)
    load = jnp.asarray([10, 2, 4, 0], jnp.int32)          # mean 4
    got = np.asarray(moe_balance(bias, load, 1e-3))
    np.testing.assert_allclose(got, [0.5 - 1e-3, -0.25 + 1e-3, 0.0,
                                     0.125 + 1e-3], rtol=0, atol=1e-7)


@pytest.mark.parametrize("held", [(0, 4), (5, 3), (13, 3)])
def test_the_load_over_all_experts_gives_the_held_groups(held):
    """Counted over all the router's experts, the held experts' slice of the
    load is what the held-only count gives: the same slots, groups and live
    rows, and each expert's slots."""
    experts = jax.random.randint(jax.random.PRNGKey(3), (40, 3), 0, 16)
    plain = moe_dispatch(experts, held, 60)
    slot, group, live, counts = moe_dispatch(experts, held, 60, total=16)
    for a, b in zip(plain[:3], (slot, group, live)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    load = np.bincount(np.asarray(experts).reshape(-1), minlength=16)
    np.testing.assert_array_equal(np.asarray(counts["load"]), load)
    assert int(counts["held_slots"]) == int(plain[3]["held_slots"]) == \
        load[held[0]:held[0] + held[1]].sum()
    assert "load" not in plain[3]


def test_the_step_balances_the_bias_outside_the_gradient():
    """Three steps of the cut's step: Adam has no state for the bias, which
    moves by exactly the rate, against the step's own load, each step;
    every other leaf is trained."""
    config = _tiny()
    model, params = _model(config, rows=96 * 3)
    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    step, shard, init = model.make_train_step(mesh, lr=1e-3, use_sp=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 96)
    state = shard(params)
    opt = init(state)
    biases = sorted(k for k in params if k.endswith("_e_bias"))
    assert biases == [f"layer{i}_e_bias" for i in (2, 3, 4, 5)]
    assert not set(biases) & set(opt)
    losses = []
    for i in range(3):
        before = {k: np.asarray(state[k]) for k in state}
        state, opt, loss, routed = step(state, opt, tokens,
                                        jnp.roll(tokens, -1, 1), i)
        losses.append(float(loss))
        load = np.asarray(routed["load"])
        assert load.shape == (4, 16) and (load.sum(1) == 2 * 48 * 3).all()
        for j, k in enumerate(biases):
            want = before[k] + 1e-3 * np.sign(load[j].mean() - load[j])
            np.testing.assert_allclose(np.asarray(state[k]), want, rtol=0,
                                       atol=1e-7)
        assert np.any(np.asarray(state["layer2_router"])
                      != before["layer2_router"])
    assert losses[2] < losses[0]


def test_four_shares_of_the_experts_sum_to_the_uncut_layer():
    """The guide's test of the cut: the routed parts that the four ranks of
    a layer's experts compute (4 of 16 each, the router and its bias whole on
    every rank) add up to the uncut reference's MLP of that layer."""
    whole = _tiny(held=(0, 16))
    model, params = _model(whole)
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 32))
    with jax.default_matmul_precision("highest"):
        mine = {k[len("layer3_"):]: v for k, v in params.items()
                if k.startswith("layer3_")}
        want, _ = reference._experts(mine, h[0], whole, jnp.matmul, (), None)
    total = 0
    for rank in range(4):
        part = _tiny(held=(4 * rank, 4))
        share = TransformerLM(family.model_config(
            part, dict(dtype="float32", remat=False)))
        sliced = dict(params)
        for k in ("layer3_e_gate_in", "layer3_e_out"):
            sliced[k] = params[k][4 * rank:4 * rank + 4]
        total = total + share._experts(sliced, "layer3_", h)[0]
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_gpt2s_logits_are_bit_for_bit_with_the_defaults():
    """A GPT-2-style model (every default) draws what it drew and computes
    what it computed: the layer list's new switches are off. The exact
    numbers are the program's before the "conv" mixer, the QK-norm and gate
    switches and the expert bias came in; the block written out beside them
    says what they are."""
    model = TransformerLM(TransformerConfig(vocab_size=64, d_model=32,
                                            n_heads=4, n_layers=2, d_ff=64,
                                            max_len=16, dtype="float32",
                                            flash_attention=False))
    params = model.init_params(jax.random.PRNGKey(0))
    assert sorted(params) == sorted(
        ["embed", "pos_embed", "lnf_g", "lnf_b"]
        + [f"layer{i}_{w}" for i in range(2) for w in (
            "ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "ln2_g", "ln2_b",
            "w_in", "w_out")])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    got = np.asarray(model.apply(params, tokens))
    assert got.dtype == np.float32
    assert [float(got.sum()), float(got[0, 0, 0]), float(got[1, 15, 63]),
            float(np.abs(got).max())] == [
        -0.3493385314941406, -0.4548521339893341, -1.2300127744674683,
        3.486454963684082]
    # the same block written out: LayerNorm, causal MHA, GELU MLP, tied head
    def ln(x, g, b):
        m = x.mean(-1, keepdims=True)
        return (x - m) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5) * g + b
    x = params["embed"][tokens] + params["pos_embed"][jnp.arange(16)]
    for i in range(2):
        p = lambda w: params[f"layer{i}_{w}"]
        h = ln(x, p("ln1_g"), p("ln1_b"))
        q, k, v = ((h @ p(w)).reshape(2, 16, 4, 8) for w in ("wq", "wk",
                                                             "wv"))
        s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(8)
        s = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), s, -jnp.inf)
        a = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(2, 16, 32) @ p("wo")
        h = ln(x, p("ln2_g"), p("ln2_b"))
        x = x + jax.nn.gelu(h @ p("w_in")) @ p("w_out")
    want = ln(x, params["lnf_g"], params["lnf_b"]) @ params["embed"].T
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


def test_the_configuration_is_568_6_million_parameters():
    config = cells.resolve(CELL).config
    model = TransformerLM(family.model_config(
        config, dict(dtype="bfloat16", remat=True, expert_rows=32768)))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    total = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert total == config["parameters"] == 568_647_936
    routed = sum(int(np.prod(v.shape)) for k, v in shapes.items()
                 if "_e_gate_in" in k or "_e_out" in k)
    assert routed == 4 * 8 * 3 * 2048 * 1792 == 352_321_536
    assert shapes["embed"].shape == (16384, 2048) and "head" not in shapes
    assert shapes["layer0_w_bcx"].shape == (2048, 6144)
    assert shapes["layer0_conv_w"].shape == (3, 2048)
    assert shapes["layer0_w_in"].shape == (2048, 7168)
    assert shapes["layer2_wq"].shape == (2048, 2048)
    assert shapes["layer2_wk"].shape == (2048, 512)
    assert shapes["layer2_q_norm_g"].shape == (64,)
    assert shapes["layer2_router"].shape == (2048, 32)
    assert shapes["layer2_e_bias"].shape == (32,)
    assert shapes["layer2_e_bias"].dtype == jnp.float32
    assert shapes["layer5_e_gate_in"].shape == (8, 2048, 3584)
    assert "layer2_wg" not in shapes and "layer1_router" not in shapes
    assert total * 12 == pytest.approx(6.82e9, rel=1e-3)


def test_a_conv_layer_has_no_sp_or_tp_path():
    config = _tiny()
    model, params = _model(config)
    with pytest.raises(NotImplementedError, match="conv"):
        model._attn(params, "layer0_", jnp.zeros((1, 8, 32)), "sp", None,
                    None)
