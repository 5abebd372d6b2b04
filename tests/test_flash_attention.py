"""Pallas flash attention vs the attention_reference oracle. On the CPU
mesh the kernel runs in Pallas interpret mode — the same kernel code path
that compiles via Mosaic on TPU."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.parallel.flash_attention import (flash_attention,
                                                          pallas_available)
from incubator_mxnet_tpu.parallel.ring_attention import attention_reference


def _qkv(B=2, T=128, H=4, D=64, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(dtype))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_grads_match_reference():
    import jax as _jax
    # real-chip f32 matmuls accumulate in different block order than the
    # dense reference; ~2e-4 abs is expected there
    atol = 5e-4 if _jax.default_backend() == "tpu" else 1e-4
    q, k, v = _qkv(T=64)

    def loss_fa(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=atol)


def test_sm_scale_and_jit():
    q, k, v = _qkv(T=64)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=False,
                                                sm_scale=0.5))
    out = f(q, k, v)
    ref = attention_reference(q, k, v, causal=False, sm_scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_cross_attention_lengths():
    # Tq != Tk (cross attention) — kv blocks iterate the key length
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 64, 4, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 128, 4, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 128, 4, 64).astype(np.float32))
    out = flash_attention(q, k, v, causal=False)
    ref = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_ragged_shape_falls_back():
    # T=100 doesn't tile; wrapper must fall back to the reference path
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 100, 2, 31).astype(np.float32))
    out = flash_attention(q, q, q, causal=True)
    ref = attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_bf16_path():
    q, k, v = _qkv(T=64)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True).astype(jnp.float32)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_transformer_flash_flag():
    from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                        TransformerLM)
    cfg = TransformerConfig(vocab_size=64, d_model=64, n_heads=2, n_layers=1,
                            d_ff=128, max_len=64, dtype="float32",
                            remat=False, flash_attention=True)
    cfg_ref = TransformerConfig(vocab_size=64, d_model=64, n_heads=2,
                                n_layers=1, d_ff=128, max_len=64,
                                dtype="float32", remat=False)
    m1, m2 = TransformerLM(cfg), TransformerLM(cfg_ref)
    params = m1.init_params(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 64), jnp.int32)
    o1 = m1.apply(params, tokens)
    o2 = m2.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-5)


def test_pallas_available_reports():
    assert isinstance(pallas_available(), bool)


def test_blocked_backward_path():
    """T large enough that the scan-over-q-blocks backward engages
    (bq < Tq), not the dense fallback."""
    q, k, v = _qkv(B=1, T=512, H=2, D=64, seed=3)

    def loss_fa(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_blocked_backward_noncausal_cross():
    q, _, _ = _qkv(B=1, T=512, H=2, D=64, seed=4)
    _, k, v = _qkv(B=1, T=256, H=2, D=64, seed=5)

    def loss_fa(q, k, v):
        return (flash_attention(q, k, v, causal=False) * 0.5).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=False) * 0.5).sum()

    g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_blocked_backward_bf16_grad_parity():
    """The blocked backward's matmuls run bf16-operand/f32-accumulate; a
    T large enough to take the SCAN path (not the dense fallback) in bf16
    must still track the reference gradients within mixed-precision
    tolerance."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel.flash_attention import flash_attention
    from incubator_mxnet_tpu.parallel.ring_attention import attention_reference

    rng = np.random.RandomState(0)
    B, T, H, D = 1, 1024, 2, 32
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)).astype(jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)).astype(jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)).astype(jnp.bfloat16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        a32 = np.asarray(a, dtype=np.float32)
        b32 = np.asarray(b, dtype=np.float32)
        scale = max(1e-3, np.abs(b32).max())
        err = np.abs(a32 - b32).max() / scale
        assert err < 0.05, (name, err)


def test_scan_fallback_backward(monkeypatch):
    """Force the no-pallas path: the XLA lax.scan backward fallback must
    still produce reference-matching gradients (it covers unimportable
    pallas and untileable shapes in production)."""
    import jax
    import jax.numpy as jnp
    import importlib
    FA = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    from incubator_mxnet_tpu.parallel.ring_attention import \
        attention_reference

    monkeypatch.setattr(FA, "pallas_available", lambda: False)
    rng = np.random.RandomState(0)
    B, T, H, D = 1, 512, 2, 32
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))

    gf = jax.grad(lambda q, k, v: jnp.sum(
        FA.flash_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        attention_reference(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_hop_vjp_includes_lse_cotangent():
    """flash_hop is differentiable in BOTH outputs; the lse cotangent
    enters the kernels' delta term (ring-attention merge consumes lse,
    so d lse must flow — a zero-dlse backward would silently drop it)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel.flash_attention import flash_hop

    rng = np.random.RandomState(0)
    B, T, H, D = 1, 64, 2, 16
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3
    sm = 1.0 / np.sqrt(D)

    def ref(q_, k_, v_):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) * sm
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v_)
        return out, lse

    def loss_flash(q_, k_, v_):
        out, lse = flash_hop(q_, k_, v_, False, sm)
        # touches BOTH outputs with different weights
        return jnp.sum(out ** 2) + 0.7 * jnp.sum(jnp.sin(lse))

    def loss_ref(q_, k_, v_):
        out, lse = ref(q_, k_, v_)
        return jnp.sum(out ** 2) + 0.7 * jnp.sum(jnp.sin(lse))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


def test_ring_attention_grad_matches_dense(monkeypatch):
    """Gradients THROUGH the flash-hop ring match autodiff of the dense
    reference on the same sharded setup."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from incubator_mxnet_tpu.parallel.ring_attention import (
        attention_reference, ring_attention_sharded)

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    rng = np.random.RandomState(1)
    B, T, H, D = 1, 256, 2, 16
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3

    def loss_ring(q_, k_, v_):
        return jnp.sum(ring_attention_sharded(q_, k_, v_, mesh,
                                              causal=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(attention_reference(q_, k_, v_, causal=True) ** 2)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


def test_flash_attention_bh_layout():
    """(BH,T,D) entry matches the (B,T,H,D) one, values and grads."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel.flash_attention import (
        flash_attention, flash_attention_bh)

    rng = np.random.RandomState(0)
    B, T, H, D = 2, 64, 2, 16
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    out1 = flash_attention(q, k, v, causal=True)
    out2 = flash_attention_bh(to_bh(q), to_bh(k), to_bh(v), causal=True)
    np.testing.assert_allclose(np.asarray(to_bh(out1)), np.asarray(out2),
                               rtol=1e-4, atol=1e-5)

    g1 = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, causal=True) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda a, b, c: jnp.sum(flash_attention_bh(
        a, b, c, causal=True) ** 2), argnums=(0, 1, 2))(
        to_bh(q), to_bh(k), to_bh(v))
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(to_bh(a)), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


# -- the causal walk inside a grid block (PR 26) ------------------------------
# (Tq, Tk, causal, entry, sub-blocks run, in the square) at the sub-block
# edge the module chose, 256: a square of n x n sub-blocks runs n (n + 1) / 2.
_WALK_CASES = {
    "cell_T1024": (1024, 1024, True, "attention", 10, 16),
    "one_block_T512": (512, 512, True, "attention", 3, 4),
    # 2 x 2 grid blocks: one below the diagonal (16 whole), two on it (10
    # each), one above (skipped by the grid)
    "grid_T2048": (2048, 2048, True, "attention", 36, 64),
    "noncausal_T1024": (1024, 1024, False, "attention", 0, 0),
    # top-left aligned: q row r sees k rows <= r, the right half of k never
    "cross_Tq512_Tk1024": (512, 1024, True, "attention", 3, 8),
    # blocks (1024, 512): the k block at 512 straddles q block 0 off its
    # corner and takes one masked pass (8 sub-blocks); both k blocks below
    # q block 1 run whole; the two on the diagonal run 7 of 8 each
    "straddle_Tq2048_Tk1536": (2048, 1536, True, "attention", 38, 48),
    # 384 = 1.5 x 256: three sub-blocks of 128 a side; a block with fewer
    # than two a side takes one masked pass
    "edge128_T384": (384, 384, True, "attention", 6, 9),
    "no_walk_T128": (128, 128, True, "attention", 1, 1),
    "hop_T1024": (1024, 1024, True, "hop", 10, 16),
}


def _dense_with_lse(q, k, v, causal, sm, window=None):
    """(out, lse) of dense attention in float32, (B, T, H, D) and (B, H,
    T): what one ring hop returns. `window`: query i reads keys j with
    0 <= i - j < window."""
    f32 = lambda x: x.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", f32(q), f32(k)) * sm
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    if window is not None:
        gap = jnp.arange(s.shape[-2])[:, None] - jnp.arange(s.shape[-1])
        s = jnp.where(gap < window, s, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), f32(v)),
            jax.scipy.special.logsumexp(s, axis=-1))


def _losses(fa, entry, causal, tq, tk, sm):
    """(flash, dense): a scalar of the kernels' results and the same of the
    dense reference, as functions of q, k, v (B, T, H, D). `hop` is
    flash_hop with a cotangent on its lse too."""
    f32 = lambda x: x.astype(jnp.float32)
    if entry == "hop":
        def flash(q_, k_, v_):
            out, lse = fa.flash_hop(q_, k_, v_, causal, sm)
            return jnp.sum(f32(out) ** 2) + 0.7 * jnp.sum(jnp.sin(lse))

        def dense(q_, k_, v_):
            out, lse = _dense_with_lse(q_, k_, v_, causal, sm)
            return jnp.sum(out ** 2) + 0.7 * jnp.sum(jnp.sin(lse))
    else:
        def flash(q_, k_, v_):
            return jnp.sum(f32(fa.flash_attention(
                q_, k_, v_, causal=causal)) ** 2)

        def dense(q_, k_, v_):
            return jnp.sum(f32(attention_reference(
                q_, k_, v_, causal=causal)) ** 2)
    return flash, dense


def _against_dense(fa, entry, causal, q, k, v, tol):
    """The loss and dq, dk, dv of the kernels against the dense reference's,
    each within `tol` of the reference's largest; returns what
    dispatch_stats() counted while the kernels were traced."""
    flash, dense = _losses(fa, entry, causal, q.shape[1], k.shape[1],
                           1.0 / np.sqrt(q.shape[3]))
    before = fa.dispatch_stats()
    got = jax.value_and_grad(flash, argnums=(0, 1, 2))(q, k, v)
    after = fa.dispatch_stats()
    want = jax.value_and_grad(dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("loss", "dq", "dk", "dv"),
                          (got[0],) + got[1], (want[0],) + want[1]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.abs(a - b).max() / max(1e-3, np.abs(b).max())
        assert err < tol, (name, err)
    return {key: after[key] - before[key] for key in after}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_causal_walk_matches_reference(case, dtype):
    """Forward and all three gradients against the dense reference over the
    shapes the sub-block walk tells apart, and the counts that say which
    sub-blocks ran. `hop` is flash_hop with a cotangent on its lse too."""
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    tq, tk, causal, entry, run, square = _WALK_CASES[case]
    assert fa._SUB == 256, "recount _WALK_CASES"
    rng = np.random.RandomState(7)
    B, H, D = 1, 2, 64
    q, k, v = (jnp.asarray(rng.randn(B, t, H, D).astype(np.float32) * 0.5
                           ).astype(dtype) for t in (tq, tk, tk))
    took = _against_dense(fa, entry, causal, q, k, v,
                          2e-4 if dtype == "float32" else 4e-2)
    assert (took["causal_subblocks_run"], took["causal_subblocks_all"]) \
        == (run, square)


# -- the layout the kernels index (PR 33) -------------------------------------
# (B, H, D, Tq, Tk, causal, entry, direct): over a caller's (B, T, H * D) a
# block of 128 lanes holds 128 // D whole heads; a shape that does not pack so
# is transposed to (B * H, T, D), one head a block, into the same kernels.
_LAYOUT_CASES = {
    # two 64-wide heads a block, two blocks a row; T 1,024 is one grid block
    # a pair (the walk, no scratch), T 2,048 two a side (running statistics
    # a head), T 256 one masked pass
    "pairs_T1024_causal": (1, 4, 64, 1024, 1024, True, "attention", True),
    "pairs_T1024_full": (1, 4, 64, 1024, 1024, False, "attention", True),
    "pairs_T2048_causal": (1, 2, 64, 2048, 2048, True, "attention", True),
    "pairs_T2048_full": (1, 2, 64, 2048, 2048, False, "attention", True),
    "pairs_batch2_T256": (2, 4, 64, 256, 256, True, "attention", True),
    "pairs_cross_causal": (1, 4, 64, 512, 1024, True, "attention", True),
    "pairs_cross_full": (2, 2, 64, 256, 512, False, "attention", True),
    "fours_D32_T512": (1, 4, 32, 512, 512, True, "attention", True),
    # one head of 128 lanes a block
    "one_D128_T1024_causal": (1, 2, 128, 1024, 1024, True, "attention", True),
    "one_D128_T2048_full": (1, 2, 128, 2048, 2048, False, "attention", True),
    # the transposed route: an odd head count, a head that is no divisor of
    # 128, a pair's worth of lanes with no second head
    "odd_H3_T1024_causal": (1, 3, 64, 1024, 1024, True, "attention", False),
    "odd_H3_T2048_full": (1, 3, 64, 2048, 2048, False, "attention", False),
    "D80_T1024_causal": (1, 2, 80, 1024, 1024, True, "attention", False),
    "D80_cross_full": (2, 2, 80, 256, 512, False, "attention", False),
    "alone_H1_T512": (2, 1, 64, 512, 512, True, "attention", False),
    # flash_hop, a cotangent on lse too: the kernels' dlse operand, a head
    # a row
    "hop_pairs_T1024_causal": (1, 4, 64, 1024, 1024, True, "hop", True),
    "hop_pairs_T2048_full": (1, 2, 64, 2048, 2048, False, "hop", True),
    "hop_odd_T512_causal": (1, 3, 64, 512, 512, True, "hop", False),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_LAYOUT_CASES))
def test_layouts_match_reference(case, dtype):
    """The output (through the loss) and dq, dk, dv against the dense
    reference over the layouts the shape rule tells apart, and the count
    that says which route the traced call took."""
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    B, H, D, tq, tk, causal, entry, direct = _LAYOUT_CASES[case]
    rng = np.random.RandomState(33)
    q, k, v = (jnp.asarray(rng.randn(B, t, H, D).astype(np.float32) * 0.5
                           ).astype(dtype) for t in (tq, tk, tk))
    # bf16: this file's bound for gradients through bf16 probabilities (the
    # transposed route read 0.041 on one draw, before PR 33 as after)
    took = _against_dense(fa, entry, causal, q, k, v,
                          2e-4 if dtype == "float32" else 5e-2)
    assert (took["direct"], took["transposed"], took["reference"]) \
        == ((1, 0, 0) if direct else (0, 1, 0))


@pytest.mark.parametrize("heads, d, direct", [
    (16, 64, True), (8, 64, True), (2, 128, True), (3, 256, True),
    (8, 16, True), (4, 32, True),
    (3, 64, False), (1, 64, False), (2, 80, False), (6, 32, False),
    (2, 96, False), (12, 8, False)])
def test_the_route_is_read_off_the_shape(heads, d, direct):
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    assert fa._direct(heads, d) is direct
    if direct:
        w = fa._lane_block(heads * d, d)
        assert w % 128 == 0 and w % d == 0 and (heads * d) % w == 0
    assert fa._lane_block(d, d) == d       # the transposed route's block


def test_layout_output_matches_reference_elementwise():
    """A pair of heads' output rows one by one, and the block beside it:
    each head's lanes hold its own result, not its neighbour's."""
    q, k, v = _qkv(B=2, T=512, H=4, D=64, seed=13)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(attention_reference(q, k, v, causal=True)),
        rtol=1e-4, atol=1e-5)


def test_causal_walk_output_matches_reference_elementwise():
    """The cell's shape, output rows compared one by one (the loss above
    sums them): every q sub-block's carried max / sum / accumulator."""
    q, k, v = _qkv(B=1, T=1024, H=2, D=64, seed=11)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(attention_reference(q, k, v, causal=True)),
        rtol=1e-4, atol=1e-5)


# -- what the forward leaves for its backward (PR 35) -------------------------
# (N, T, C, d, causal): lse, delta and dlse are (N * C // d, 1, T) f32, a
# head a row of lanes, whatever the heads a block (g) and the grid.
_ROW_CASES = {
    "g2_one_block": (2, 256, 256, 64, True),
    "g2_walked_block": (1, 1024, 128, 64, True),
    "g2_several_blocks": (1, 2048, 128, 64, True),
    "g1_one_block": (2, 256, 128, 128, False),
    "g1_several_blocks": (1, 2048, 128, 128, True),
    "g1_half_a_tile": (3, 512, 64, 64, True),      # the transposed route's
}


def _kernel_operands(case, dtype=np.float32):
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    n, t, c, d, causal = _ROW_CASES[case]
    rng = np.random.RandomState(35)
    q, k, v, do = (jnp.asarray(rng.randn(n, t, c).astype(np.float32) * 0.5
                               ).astype(dtype) for _ in range(4))
    block = fa._pick_block(t)
    return fa, (q, k, v, do), (d, causal, 1.0 / np.sqrt(d), block, block,
                               fa._interpret())


@pytest.mark.parametrize("case", list(_ROW_CASES))
def test_the_row_vectors_are_rows_of_lanes(case):
    """lse comes out of the forward kernel as (N * C // d, 1, T), head h of
    row b at b * (C // d) + h, and holds the dense log-sum-exp."""
    fa, (q, k, v, _), static = _kernel_operands(case)
    n, t, c, d, causal = _ROW_CASES[case]
    out, lse = fa._fa_forward(q, k, v, *static)
    assert out.shape == (n, t, c)
    assert lse.shape == (n * c // d, 1, t) and lse.dtype == jnp.float32
    heads = lambda x: x.reshape(n, t, c // d, d)
    s = jnp.einsum("bqhd,bkhd->bhqk", heads(q), heads(k)) / np.sqrt(d)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    np.testing.assert_allclose(
        np.asarray(lse).reshape(n, c // d, t),
        np.asarray(jax.scipy.special.logsumexp(s, axis=-1)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["g2_walked_block", "g2_several_blocks",
                                  "g1_one_block"])
def test_no_lse_cotangent_is_a_zero_one_bit_for_bit(case, dtype):
    """Plain attention hands the dq kernel no dlse operand; a ring hop
    hands it one. With zeros in it the three gradients are the same bits."""
    fa, (q, k, v, do), static = _kernel_operands(case, dtype)
    out, lse = fa._fa_forward(q, k, v, *static)
    none = fa._fa_backward(q, k, v, do, lse, out, None, *static)
    zeros = fa._fa_backward(q, k, v, do, lse, out, jnp.zeros_like(lse),
                            *static)
    for a, b in zip(none, zeros):
        assert a.dtype == b.dtype and a.shape == q.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_block_that_keeps_the_flash_residuals_is_the_unkept_block(dtype):
    """Value and gradient of one `jax.checkpoint`ed TransformerLM block
    under the model's own policy (the kernel's output and lse kept, the
    forward kernel traced once) against a checkpoint that keeps nothing
    (traced twice): the kept residuals are the same kernel's on the same
    operands, so every number is the same bits."""
    from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                        TransformerLM,
                                                        _remat_policy)
    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=256, n_heads=4, n_layers=1, d_ff=128,
        max_len=256, dtype=dtype, remat=True, flash_attention=True))
    params = {k: v for k, v in
              model.init_params(jax.random.PRNGKey(0)).items()
              if k.startswith("layer0_")}
    x = jnp.asarray(np.random.RandomState(5).randn(2, 256, 256) * 0.5,
                    dtype)
    body = lambda p, y: model._block(p, "layer0_", y, None)

    def value_and_grad(policy):
        block = jax.checkpoint(body, policy=policy)
        f = jax.value_and_grad(
            lambda p, y: jnp.sum(block(p, y).astype(jnp.float32) ** 2),
            argnums=(0, 1))
        return f(params, x), str(jax.make_jaxpr(f)(params, x))
    kept, kept_jaxpr = value_and_grad(_remat_policy(None))
    unkept, unkept_jaxpr = value_and_grad(None)
    assert kept_jaxpr.count("name=flash_fwd") == 1
    assert unkept_jaxpr.count("name=flash_fwd") == 2
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(unkept)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# -- one backward call (PR 37: one grid block; PR 39: every causal grid) -------
# (B, H, D, Tq, Tk, causal, dlse[, blocks a side, window]): `_fa_backward`
# makes ONE call, the dk/dv kernel's walk giving dq as well, where the call is
# one grid block in q and in k (blocks as long as the two lengths), and where
# it is causal self-attention over several, plain or a band, the head's dq
# carried across the k blocks in VMEM; `_fa_backward_pair` is the pair it
# always made, on the same operands in the same blocks. `dlse` is a ring
# hop's cotangent of lse; an odd head count and D = 80 take the transposed
# route, one head a block. A band of `n_k` steps a k block has, at the
# sequence's last n_k - 1 k blocks, steps past its edge, which run nothing.
_ONE_BLOCK_CASES = {
    "causal_T256": (2, 2, 64, 256, 256, True, False),
    "full_T256": (1, 2, 64, 256, 256, False, False),
    "causal_T512": (1, 4, 64, 512, 512, True, False),
    "full_T512_D128": (1, 2, 128, 512, 512, False, False),
    "causal_T1024": (1, 2, 64, 1024, 1024, True, False),
    "full_T1024": (1, 2, 64, 1024, 1024, False, False),
    "causal_T1024_D128": (1, 1, 128, 1024, 1024, True, False),
    "causal_Tq512_Tk1024": (1, 2, 64, 512, 1024, True, False),
    "causal_Tq1024_Tk512": (1, 2, 64, 1024, 512, True, False),
    "full_Tq256_Tk512": (2, 2, 64, 256, 512, False, False),
    "hop_causal_T1024": (1, 2, 64, 1024, 1024, True, True),
    "hop_full_T512_D128": (1, 2, 128, 512, 512, False, True),
    "hop_causal_Tq512_Tk1024": (1, 2, 64, 512, 1024, True, True),
    "odd_H3_causal_T1024": (1, 3, 64, 1024, 1024, True, False),
    "odd_H3_hop_full_T512": (1, 3, 64, 512, 512, False, True),
    "D80_causal_T512": (1, 2, 80, 512, 512, True, False),
    # several grid blocks: 2 x 2 and 4 x 4, sub-blocks of 128 inside them
    "grid2_causal_T512": (2, 2, 64, 512, 512, True, False, 2, None),
    "grid2_causal_T512_D128": (1, 2, 128, 512, 512, True, False, 2, None),
    "grid4_causal_T1024": (1, 2, 64, 1024, 1024, True, False, 4, None),
    "grid4_causal_T1024_D128": (1, 1, 128, 1024, 1024, True, False, 4, None),
    "grid4_causal_T512_no_sub_blocks": (1, 2, 64, 512, 512, True, False, 4,
                                        None),
    "grid2_hop_causal_T512": (1, 2, 64, 512, 512, True, True, 2, None),
    "grid4_hop_causal_T1024_D128": (1, 1, 128, 1024, 1024, True, True, 4,
                                    None),
    "grid2_odd_H3_causal_T512": (1, 3, 64, 512, 512, True, False, 2, None),
    "grid2_D80_hop_causal_T512": (1, 2, 80, 512, 512, True, True, 2, None),
    # bands: n_k 2 (window 200 in blocks of 256), n_k 3 = every block of
    # T 768 (window 600), the narrowest that has a gradient, one grid block
    "band_nk2_T1024": (1, 2, 64, 1024, 1024, True, False, 4, 200),
    "band_nk2_T1024_D128": (1, 1, 128, 1024, 1024, True, False, 4, 200),
    "band_nk3_T768_D128": (2, 1, 128, 768, 768, True, False, 3, 600),
    "band_nk2_T512_no_sub_blocks": (1, 2, 64, 512, 512, True, False, 4, 130),
    "band_of_two_keys_T512": (1, 2, 64, 512, 512, True, False, 2, 2),
    "band_one_block_T512": (1, 2, 64, 512, 512, True, False, 1, 300),
    "band_nk2_odd_H3_T512": (1, 3, 64, 512, 512, True, False, 2, 256),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_ONE_BLOCK_CASES))
def test_one_block_backward_matches_the_pair_and_the_reference(case, dtype):
    """dq, dk, dv of the one call against the pair's on the same operands
    (no switch: the pair is a function of its own) and against `jax.vjp` of
    the dense float32 reference, lse's cotangent with it where the case is
    a hop; dispatch_stats() says that `_fa_backward` took the one call."""
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    B, H, D, tq, tk, causal, hop, grid, window = \
        (_ONE_BLOCK_CASES[case] + (1, None))[:9]
    rng = np.random.RandomState(37)
    q, k, v, do = (jnp.asarray(rng.randn(B, t, H, D).astype(np.float32) * 0.5
                               ).astype(dtype) for t in (tq, tk, tk, tq))
    dlse = jnp.asarray(rng.randn(B, H, tq).astype(np.float32) * 0.3) \
        if hop else None
    sm = 1.0 / np.sqrt(D)
    direct = fa._direct(H, D)
    ops = [fa._operand(x, direct) for x in (q, k, v, do)]
    static = (D, causal, sm, tq // grid, tk // grid, fa._interpret(), window)
    out, lse = fa._fa_forward(*ops[:3], *static)
    rows = None if dlse is None else dlse.reshape(B * H, 1, tq)

    def backward(which):
        before = fa.dispatch_stats()
        got = which(*ops, lse, out, rows, *static)
        after = fa.dispatch_stats()
        return [np.asarray(fa._result(g, like, direct), np.float32)
                for g, like in zip(got, (q, k, v))], \
            (after["bwd_fused"] - before["bwd_fused"],
             after["bwd_pair"] - before["bwd_pair"])
    one, took_one = backward(fa._fa_backward)
    pair, took_pair = backward(fa._fa_backward_pair)
    # the router counts; the pair called by name is no routed call
    assert (took_one, took_pair) == ((1, 0), (0, 0))
    _, vjp = jax.vjp(lambda *a: _dense_with_lse(*a, causal, sm, window),
                     q, k, v)
    want = vjp((do.astype(jnp.float32),
                jnp.zeros((B, H, tq)) if dlse is None else dlse))
    # against the reference: this file's bounds for the pair; against the
    # pair: the same products of the same bf16 probabilities, summed in
    # another order
    to_ref, to_pair = (2e-4, 2e-5) if dtype == "float32" else (5e-2, 1e-2)
    for name, a, b, c in zip(("dq", "dk", "dv"), one, pair, want):
        c = np.asarray(c, np.float32)
        scale = max(1e-3, np.abs(c).max())
        assert np.abs(a - b).max() / scale < to_pair, (name, "pair")
        assert np.abs(a - c).max() / scale < to_ref, (name, "reference")


def test_the_pair_is_still_what_a_call_past_the_one_call_gets():
    """A non-causal call of several grid blocks through `_fa_backward` is the
    pair, bit for bit: dq of a q block is final only at the last k block."""
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    rng = np.random.RandomState(39)
    ops = [jnp.asarray(rng.randn(1, 512, 128).astype(np.float32) * 0.5)
           for _ in range(4)]
    static = (64, False, 0.125, 256, 256, fa._interpret())
    out, lse = fa._fa_forward(*ops[:3], *static)
    before = fa.dispatch_stats()
    routed = fa._fa_backward(*ops, lse, out, None, *static)
    after = fa.dispatch_stats()
    assert (after["bwd_fused"], after["bwd_pair"]) == \
        (before["bwd_fused"], before["bwd_pair"] + 1)
    for a, b in zip(routed, fa._fa_backward_pair(*ops, lse, out, None,
                                                 *static)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape, kw, fused", [
    ((2, 1024, 4, 64), {}, True),
    ((1, 2048, 2, 64), {}, True),
    ((1, 1024, 2, 64), {"window": 256}, True),
    ((1, 512, 3, 64), {}, True),
    ((1, 8192, 2, 128), {"window": 512}, True),
    ((1, 65536, 1, 128), {}, True),
    ((1, 131072, 1, 128), {}, False),
    ((1, 2048, 2, 64), {"causal": False}, False)],
    ids=["one_block_T1024", "grid_T2048", "window_T1024", "transposed_T512",
         "window_T8192", "grid_T65536", "past_the_vmem_budget_T131072",
         "not_causal_T2048"])
def test_the_backward_path_is_read_off_the_lengths(shape, kw, fused):
    """Which backward a traced call took, by dispatch_stats(): one call
    where the two lengths are one grid block each, and where the call is
    causal self-attention, plain or a band, whose dq (T, 128) f32 fits half
    the chip's VMEM with the step's buffers; the pair past that (a 128k ring
    shard: 64 MiB of dq) and for a non-causal grid. Counted at trace time:
    nothing runs here."""
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kw = {"causal": True, **kw}
    before = fa.dispatch_stats()
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: fa.flash_attention(*a, **kw)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, q, q))
    after = fa.dispatch_stats()
    assert (after["bwd_fused"] - before["bwd_fused"],
            after["bwd_pair"] - before["bwd_pair"]) \
        == ((1, 0) if fused else (0, 1))
    win = "win_" if "window" in kw else ""
    assert (f"name=flash_{win}bwd_dkv" in text) is not fused
    assert f"name=flash_{win}bwd_dq" in text
    if fused and shape[1] > 1024:
        # the k blocks carry dq, so they run in order; the call names its
        # VMEM, the forward kernel's none
        assert "('parallel', 'parallel', 'arbitrary', 'arbitrary')" in text
        assert re.findall(r"vmem_limit_bytes=(\w+)", text) == [
            "None", str(max(16 * 2**20, fa._carried_vmem(
                jax.ShapeDtypeStruct((shape[0], shape[1], shape[2] * shape[3]),
                                     jnp.bfloat16), shape[3], 1024, None)))]


def test_the_one_calls_vmem_is_reckoned_from_its_buffers():
    """`_one_call_vmem` at the cells' shapes against the least limit the TPU
    compiler accepted there (AOT for a described v5e, PERF.md section 6, PR
    39): above it, by under a tenth; and the route's budget is half the
    chip's VMEM."""
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    mib = 2 ** 20
    # (tq, block, w, g, itemsize, row vectors) -> the compiler's need, MiB
    for args, need in [((8192, 1024, 128, 1, 2, 1), 15.99),
                       ((2048, 1024, 128, 2, 2, 1), 13.25),
                       ((32768, 1024, 128, 1, 2, 1), 28.06),
                       ((8192, 512, 128, 1, 2, 1), 7.875),
                       ((2048, 1024, 128, 1, 4, 1), 29.56),
                       ((2048, 1024, 128, 2, 4, 1), 27.69)]:
        got = fa._one_call_vmem(*args) / mib
        assert need <= got < 1.25 * need, (args, got)
    assert fa._one_call_vmem(8192, 1024, 128, 1, 2, 1) == 17891328
    assert fa._vmem_bytes() == 128 * mib        # no TPU here: a v5e's
    assert fa._one_call_vmem(65536, 1024, 128, 1, 2, 1) <= 64 * mib \
        < fa._one_call_vmem(131072, 1024, 128, 1, 2, 1)
