"""The expert layer's routed part as one chip of an expert-parallel
deployment computes it (`parallel/moe.moe_routed`: sort, one buffer, two
grouped products, no capacity an expert) against the one-hot oracle
`moe_gate` with its capacity out of reach: values and gradients, softmax and
sigmoid scores, both grouped products; the parts all the ranks give add up
to the uncut layer; an overflow of the buffer is counted."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from incubator_mxnet_tpu.parallel import moe

N, D, F, E, K = 48, 32, 16, 16, 3


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (N, D)),
            jax.random.normal(ks[1], (D, E)),
            jax.random.normal(ks[2], (E, D, 2 * F)) / np.sqrt(D),
            jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F))


def _expert(x, gate_in, out):
    h = x @ gate_in
    return (jax.nn.silu(h[..., :F]) * h[..., F:]) @ out


def _oracle(x, router, gate_in, out, score, scaling, norm, held=(0, E)):
    """Through `moe_gate`'s one-hot (N, E, C) queues, C = every slot: which
    experts a token chose is the oracle's (a sigmoid ranks as a softmax
    does), the weights the score's own."""
    dispatch, _, _ = moe.moe_gate(x, router, k=K, capacity_factor=E)
    assert int(dispatch.sum()) == N * K                  # nothing dropped
    chosen = dispatch.any(-1)                            # (N, E)
    logits = x @ router
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, -1)
    w = jnp.where(chosen, s, 0.0)
    if norm:
        w = w / w.sum(-1, keepdims=True)
    first, count = held
    own = slice(first, first + count)
    queues = jnp.einsum("nec,nd->ecd", dispatch[:, own].astype(x.dtype), x)
    outs = jax.vmap(_expert)(queues, gate_in[own], out[own])
    combine = dispatch[:, own] * (scaling * w[:, own, None])
    return jnp.einsum("nec,ecd->nd", combine, outs)


def _routed(x, router, gate_in, out, score, scaling, norm, held=(0, E),
            rows=N * K):
    own = slice(held[0], held[0] + held[1])
    return moe.moe_routed(x, router, gate_in[own], out[own], held=held, k=K,
                          rows=rows, score=score, scaling=scaling,
                          norm_topk=norm)


@pytest.mark.parametrize("score,scaling,norm", [
    ("softmax", 1.0, False), ("sigmoid", 2.5, True), ("softmax", 2.5, True)])
def test_values_and_gradients_match_the_one_hot_oracle(score, scaling, norm):
    args = _weights()
    y, counts = _routed(*args, score, scaling, norm)
    want = _oracle(*args, score, scaling, norm)
    assert float(jnp.max(jnp.abs(y - want))) < 2e-5
    assert int(counts["held_slots"]) == N * K
    assert int(counts["slots_over"]) == 0
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    got = jax.grad(loss(lambda *a: _routed(*a, score, scaling, norm)[0]),
                   argnums=(0, 1, 2, 3))(*args)
    ref = jax.grad(loss(lambda *a: _oracle(*a, score, scaling, norm)),
                   argnums=(0, 1, 2, 3))(*args)
    for g, r in zip(got, ref):
        assert float(jnp.max(jnp.abs(r))) > 0
        assert float(jnp.max(jnp.abs(g - r))) < 2e-4 * max(
            1.0, float(jnp.max(jnp.abs(r))))


@pytest.mark.parametrize("sizes", [(5, 11, 0, 16), (32, 0, 0, 0),
                                   (1, 1, 1, 29)])
def test_the_grouped_product_is_lax_ragged_dot(sizes):
    """The one grouped product the program has (jax's megablox kernels, in
    the interpreter here) against jax's own primitive: values and both
    gradients, with an empty group and a group of one row among them."""
    ks = jax.random.split(jax.random.PRNGKey(sum(sizes[:2])), 2)
    lhs = jax.random.normal(ks[0], (sum(sizes), D))
    rhs = jax.random.normal(ks[1], (len(sizes), D, F)) / np.sqrt(D)
    group = jnp.asarray(sizes, jnp.int32)
    loss = lambda f: lambda a, b: jnp.sum(jnp.sin(f(a, b, group)))
    for got, want in zip(
            jax.value_and_grad(loss(moe.grouped_matmul), (0, 1))(lhs, rhs),
            jax.value_and_grad(loss(jax.lax.ragged_dot), (0, 1))(lhs, rhs)):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert float(jnp.max(jnp.abs(g - w))) < 2e-5 * max(
                1.0, float(jnp.max(jnp.abs(w))))


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (12, 4), (8, 8)])
def test_a_rank_computes_its_own_experts_part_and_no_other(held):
    args = _weights(1)
    y, counts = _routed(*args, "sigmoid", 2.5, True, held, rows=96)
    want = _oracle(*args, "sigmoid", 2.5, True, held)
    assert float(jnp.max(jnp.abs(y - want))) < 2e-5
    experts, _ = moe.moe_route(args[0], args[1], K)
    mine = int(jnp.sum((experts >= held[0]) & (experts < sum(held))))
    assert int(counts["held_slots"]) == mine < 96


def test_the_ranks_parts_and_the_shared_expert_once_add_up_to_the_layer():
    """E / held = 4 ranks at this size (32 at the published sizes): their
    routed parts, with what every rank computes alike (the shared expert)
    counted once, are the uncut layer, computed here expert by expert in
    plain jax.numpy with no dispatch at all."""
    x, router, gate_in, out = _weights(2)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    s_gate_in = jax.random.normal(ks[0], (D, 2 * F)) / np.sqrt(D)
    s_out = jax.random.normal(ks[1], (F, D)) / np.sqrt(F)
    shared = _expert(x, s_gate_in, s_out)
    parts = [_routed(x, router, gate_in, out, "sigmoid", 2.5, True,
                     (r * 4, 4), rows=96)[0] for r in range(E // 4)]
    s = jax.nn.sigmoid(x @ router)
    top, chosen = jax.lax.top_k(s, K)
    w = 2.5 * top / top.sum(-1, keepdims=True)
    whole = shared
    for e in range(E):
        mine = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)
        whole = whole + mine[:, None] * _expert(x, gate_in[e], out[e])
    assert float(jnp.max(jnp.abs(sum(parts) + shared - whole))) < 2e-5
    # and a rank's part alone is not the layer
    assert float(jnp.max(jnp.abs(parts[0] + shared - whole))) > 1e-2


def test_slots_beyond_the_buffer_are_counted_never_silent():
    args = _weights(3)
    _, fits = _routed(*args, "sigmoid", 1.0, True, (0, 8), rows=N * K)
    held = int(fits["held_slots"])
    assert 0 < held < N * K and int(fits["slots_over"]) == 0
    y, over = _routed(*args, "sigmoid", 1.0, True, (0, 8), rows=held - 7)
    assert int(over["held_slots"]) == held
    assert int(over["slots_over"]) == 7
    assert bool(jnp.all(jnp.isfinite(y)))


def test_every_routing_does_the_same_work():
    """The buffer's rows, the groups' sum and every shape are the traffic's,
    wherever the tokens went: the spare rows go with the last held expert at
    weight zero."""
    for seed in range(3):
        x, router, _, _ = _weights(seed)
        experts, weights = moe.moe_route(x, router, K)
        slot, group, live, counts = moe.moe_dispatch(experts, (4, 4), 64)
        assert slot.shape == live.shape == (64,) and group.shape == (4,)
        assert int(group.sum()) == 64
        assert int(live.sum()) == int(counts["held_slots"]) < 64
        local = np.asarray(experts).reshape(-1)[np.asarray(slot)] - 4
        n_live = int(live.sum())
        assert (np.diff(local[:n_live]) >= 0).all()      # sorted by expert
        assert ((local[:n_live] >= 0) & (local[:n_live] < 4)).all()
        assert weights.shape == (N, K)


def test_a_score_the_router_does_not_know_is_refused():
    x, router, gate_in, out = _weights()
    with pytest.raises(ValueError, match="router score"):
        _routed(x, router, gate_in, out, "tanh", 1.0, True)


# -- the megablox tiles: each call from its own shape ------------------------

@pytest.mark.parametrize("size,tile", [
    (1792, 896), (3584, 896), (2048, 1024), (3072, 1024), (1024, 1024),
    (1280, 640), (640, 640), (96, 96), (200, 200)])
def test_a_width_takes_the_largest_multiple_of_128_that_divides_it(size,
                                                                   tile):
    """1,792 and 3,584 in 896-wide tiles, not 256 and 512; a width that no
    multiple of 128 divides is one tile."""
    assert moe._gmm_tiling(73728, size, 2048, 8)[1] == tile
    assert moe._gmm_tiling(73728, 2048, size, 8)[2] == tile


@pytest.mark.parametrize("m,groups,rows", [
    (73728, 8, 512), (12288, 8, 512), (8192, 8, 512), (8192 - 512, 8, 256),
    (4096, 8, 256), (4096, 1, 512), (73728 + 256, 8, 256), (96, 4, 96)])
def test_the_row_tile_follows_the_rows_a_group_averages(m, groups, rows):
    assert moe._gmm_tiling(m, 2048, 1792, groups)[0] == rows


def _two_products(f):
    """The routed part's grouped products: gate and up as one, then down."""
    def experts(x, w1, w2, group):
        h = moe.grouped_matmul(x, w1, group)
        h = (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(x.dtype)
        return moe.grouped_matmul(h, w2, group)
    return experts


@pytest.mark.parametrize("rows,d,f,tiles,steps", [
    (73728, 2048, 1792, {(512, 1024, 896): 4, (512, 896, 1024): 4}, 48),
    (12288, 3072, 1024, {(512, 1024, 1024): 8}, 36)],
    ids=["lfm2", "laguna"])
def test_grouped_stats_counts_a_remat_layers_grid_steps(rows, d, f, tiles,
                                                        steps):
    """Value and gradient of one expert layer under remat at a cell's
    shapes, traced and not run: each product's forward twice, the rows'
    gradient on its own axes (a 3,584-deep contraction in 896-wide steps,
    1,792 columns in 896-wide tiles) and `tgmm` on the forward's. LFM2's
    layer takes 48 grid steps a row tile, where 256-row tiles that fell to
    256 and 512 wide took 116; every call of Laguna's takes 512 rows by
    1,024 by 1,024."""
    S = jax.ShapeDtypeStruct
    G = 8
    args = (S((rows, d), jnp.bfloat16), S((G, d, 2 * f), jnp.bfloat16),
            S((G, f, d), jnp.bfloat16), S((G,), jnp.int32))
    experts = _two_products(f)
    loss = jax.checkpoint(
        lambda *a: experts(*a).astype(jnp.float32).sum())
    before = moe.grouped_stats()
    jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(*args)
    after = moe.grouped_stats()
    got = {t: c - before["tiles"].get(t, 0) for t, c in after["tiles"].items()
           if c > before["tiles"].get(t, 0)}
    assert got == tiles
    assert after["calls"] - before["calls"] == 8
    assert after["row_tile_steps"] - before["row_tile_steps"] == steps


def test_a_width_of_640_tiles_and_differentiates_as_lax_ragged_dot():
    """Widths whose tile is not a power of two (1,280 and 640 -> 640), the
    rows' gradient on its own axes: the layer's value and its three
    gradients against the same layer over `lax.ragged_dot`."""
    sizes = (100, 0, 156, 256)
    rows, d, f = sum(sizes), 1280, 640
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (rows, d))
    w1 = jax.random.normal(ks[1], (len(sizes), d, 2 * f)) / np.sqrt(d)
    w2 = jax.random.normal(ks[2], (len(sizes), f, d)) / np.sqrt(f)
    group = jnp.asarray(sizes, jnp.int32)
    assert moe._gmm_tiling(rows, d, 2 * f, len(sizes)) == (256, 640, 640)

    def ragged(x, w1, w2, group):
        h = jax.lax.ragged_dot(x, w1, group)
        h = jax.nn.silu(h[:, :f]) * h[:, f:]
        return jax.lax.ragged_dot(h, w2, group)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a, group)))
    got = jax.value_and_grad(loss(_two_products(f)), (0, 1, 2))(x, w1, w2)
    want = jax.value_and_grad(loss(ragged), (0, 1, 2))(x, w1, w2)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0
        assert float(jnp.max(jnp.abs(g - w))) < 2e-5 * max(
            1.0, float(jnp.max(jnp.abs(w))))
