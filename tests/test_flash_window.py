"""Windowed causal flash attention (query i reads keys j with 0 <= i - j <
window): the three kernels over the band's grid, in interpret mode, against
a masked softmax; the band's geometry against a brute count; and a call
with no window traces to the kernels it traced to before there was one."""
import hashlib
import importlib
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from incubator_mxnet_tpu.parallel.ring_attention import attention_reference

fa = importlib.import_module("incubator_mxnet_tpu.parallel.flash_attention")


def _masked_softmax(q, k, v, window):
    """The oracle, written out: no code of the program's."""
    t, d = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / np.sqrt(d)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.where((j <= i) & (i - j < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                      precision="highest")


# (T, heads, D, window): blocks are 1,024 a side from T 2,048 on and the
# whole of a shorter T; sub-blocks 256 (128 at T 256; none at T 64)
CASES = {
    "one_block_window_under_a_sub_block": (1024, 2, 128, 100),
    "one_block_window_of_a_sub_block": (1024, 2, 128, 256),
    "one_block_window_across_sub_blocks": (1024, 1, 128, 300),
    "one_block_window_of_two_sub_blocks_d64": (1024, 2, 64, 512),
    "two_blocks_window_under_a_block": (2048, 1, 128, 512),
    "two_blocks_window_of_a_block": (2048, 1, 128, 1024),
    "three_blocks_window_over_a_block": (3072, 1, 128, 1536),
    "two_blocks_window_over_the_sequence_d64": (2048, 2, 64, 4000),
    "no_sub_blocks": (64, 2, 16, 20),
    "small_sub_blocks_d64": (256, 2, 64, 128),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_gradients_match_a_masked_softmax(case):
    t, h, d, window = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(t + window), 4)
    q, k, v, do = (jax.random.normal(key, (1, t, h, d), jnp.float32)
                   for key in ks)
    before = fa.dispatch_stats()
    out, pull = jax.vjp(lambda *a: fa.flash_attention(
        *a, causal=True, window=window), q, k, v)
    want, ref_pull = jax.vjp(lambda *a: _masked_softmax(*a, window), q, k, v)
    after = fa.dispatch_stats()
    assert after["pallas"] == before["pallas"] + 1        # the kernels ran
    assert after["causal_subblocks_all"] == before["causal_subblocks_all"]
    assert float(jnp.max(jnp.abs(out - want))) < 5e-6
    for got, ref in zip(pull(do), ref_pull(do)):
        assert float(jnp.max(jnp.abs(got - ref))) < 2e-5
    # and the dense path's own mask is the same one
    assert float(jnp.max(jnp.abs(attention_reference(
        q, k, v, causal=True, window=window) - want))) < 5e-6


def test_grouped_kv_heads_under_a_window_in_bfloat16():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 512, 6, 64), jnp.bfloat16)
    k, v = (jax.random.normal(key, (2, 512, 2, 64), jnp.bfloat16)
            for key in ks[1:])
    got = fa.flash_attention(q, k, v, causal=True, window=128)
    rep = lambda x: jnp.repeat(x.astype(jnp.float32), 3, axis=2)
    want = _masked_softmax(q.astype(jnp.float32), rep(k), rep(v), 128)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 3e-2


@pytest.mark.parametrize("t,block,window", [
    (1024, 1024, 100), (1024, 1024, 300), (2048, 1024, 512),
    (3072, 1024, 1536), (8192, 1024, 512), (512, 512, 512), (64, 64, 20)])
def test_the_band_runs_the_sub_blocks_it_meets_and_no_other(t, block,
                                                            window):
    """_band_strips against a brute count over (i, j): every sub-block with a
    kept pair is run exactly once, by the q walk and by the k walk alike;
    one with none is not; a sub-block is masked if and only if an edge of
    the band crosses it."""
    band = fa._band_plan(t, block, window)
    c = band.sub or block
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    kept = (j <= i) & (i - j < window)
    tiles = kept.reshape(t // c, c, t // c, c).transpose(0, 2, 1, 3)
    some, whole = tiles.any((2, 3)), tiles.all((2, 3))
    assert band.all == some.size and band.run == int(some.sum())
    for by_k in (False, True):
        ran = np.zeros_like(some, dtype=int)
        for outer in range(t // block):
            for delta in range(band.n_k):
                other = outer + delta if by_k else outer - delta
                if not 0 <= other < t // block:
                    continue
                qb, kb = (other, outer) if by_k else (outer, other)
                for rows, cols, pieces in fa._band_strips(
                        block, c, delta, window, by_k):
                    masked = {(lo // c) for lo, *_ in pieces}
                    for n, col in enumerate(range(cols[0] // c,
                                                  cols[1] // c)):
                        a, b = rows[0] // c, col
                        qs, ks = (b, a) if by_k else (a, b)
                        at = (qb * (block // c) + qs, kb * (block // c) + ks)
                        ran[at] += 1
                        assert (n in masked) == (not whole[at]), (at, by_k)
        assert (ran == some).all(), by_k
    if t == 8192:       # the cell's windowed layers: under a fifth
        assert band.run / band.all < 0.2 and band.n_k == 2


def test_a_window_wants_causal_self_attention():
    q = jnp.zeros((1, 256, 2, 64))
    with pytest.raises(ValueError, match="causal self-attention"):
        fa.flash_attention(q, q, q, causal=False, window=64)
    with pytest.raises(ValueError, match="causal self-attention"):
        fa.flash_attention(q, q[:, :128], q[:, :128], causal=True, window=64)


def _jaxpr(shape, **kw):
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, v, do: jax.vjp(
        lambda *a: fa.flash_attention(*a, causal=True, **kw), q, k, v)[1](
            do))(q, q, q, q))
    return re.sub(r"0x[0-9a-f]+", "0x", text)


# sha256 of the jaxpr (addresses taken out) of value and gradient of a
# causal call. GPT-2 medium's call is one grid block in q and in k: its
# backward is one call under the dq kernel's name since PR 37, and its jaxpr
# is that tree's still (recorded on PR 38's commit). MiniCPM-SALA's call and
# a transposed-route shape are several blocks a side: since PR 39 their
# backward is one call too, no longer the pair of the tree before a window
# existed, and they are held to their kernels' names alone.
BEFORE_A_WINDOW = {
    (32, 1024, 16, 64): "9c3e49e673407ff1",
    (1, 8192, 32, 128): None,
    (2, 2048, 3, 80): None,
}


@pytest.mark.parametrize("shape", sorted(BEFORE_A_WINDOW))
def test_a_call_with_no_window_traces_to_the_kernels_it_always_did(shape):
    plain = _jaxpr(shape)
    if BEFORE_A_WINDOW[shape]:
        assert hashlib.sha256(plain.encode()).hexdigest()[:16] == \
            BEFORE_A_WINDOW[shape]
    assert _jaxpr(shape, window=None) == plain
    kernels = lambda text: set(re.findall(r"name=(flash_\w+)", text)) \
        - set(fa.RESIDUAL_NAMES)
    # one backward call at every length, with a band as without
    assert kernels(plain) == {"flash_fwd", "flash_bwd_dq"}
    assert kernels(_jaxpr(shape, window=512)) == {
        "flash_win_fwd", "flash_win_bwd_dq"}


# (T, heads, D, window), in blocks of 1,024 (the whole of a shorter T): the
# band's steps a k block, and whether one of them falls past the last block
BANDS = {
    "one_block": (1024, 2, 64, 300),
    "two_steps_one_past_the_edge": (2048, 1, 128, 512),
    "three_steps_two_past_the_edge_d64": (3072, 2, 64, 1536),
    "window_over_the_sequence": (2048, 1, 128, 4000),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(BANDS))
def test_a_bands_one_backward_call_is_the_pair(case, dtype):
    """dq, dk, dv of a windowed call's one backward call (the dk/dv kernel's
    walk carrying dq across the band's k blocks) against the pair's on the
    same operands: dk and dv the same bits, dq the same products summed in
    f32 in the same order."""
    t, h, d, window = BANDS[case]
    ks = jax.random.split(jax.random.PRNGKey(t + window), 4)
    q, k, v, do = (jax.random.normal(key, (1, t, h * d), jnp.float32)
                   .astype(dtype) for key in ks)
    block = fa._pick_block(t)
    static = (d, True, d ** -0.5, block, block, fa._interpret(), window)
    out, lse = fa._fa_forward(q, k, v, *static)
    before = fa.dispatch_stats()
    one = fa._fa_backward(q, k, v, do, lse, out, None, *static)
    after = fa.dispatch_stats()
    assert (after["bwd_fused"], after["bwd_pair"]) == \
        (before["bwd_fused"] + 1, before["bwd_pair"])
    pair = fa._fa_backward_pair(q, k, v, do, lse, out, None, *static)
    f32 = lambda x: np.asarray(x, np.float32)
    for got, want in zip(one[1:], pair[1:]):
        np.testing.assert_array_equal(f32(got), f32(want))
    bound = 2e-5 if dtype == "float32" else 1e-2
    assert np.abs(f32(one[0]) - f32(pair[0])).max() \
        / np.abs(f32(pair[0])).max() < bound


def test_a_block_keeps_a_windowed_calls_output_and_lse():
    """Under a checkpoint that keeps the flash residuals' names, the
    backward of a windowed call runs no forward kernel again."""
    from jax.ad_checkpoint import checkpoint_name  # noqa: F401
    policy = jax.checkpoint_policies.save_only_these_names(
        *fa.RESIDUAL_NAMES)
    q = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    body = jax.checkpoint(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=512), policy=policy)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: body(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(
            q, q, q))
    assert len(re.findall(r"name=flash_win_fwd\b", text)) == 1
    assert len(re.findall(r"name=flash_win_bwd_dq\b", text)) == 1
