"""Block-sparse causal attention by InfLLM-v2 selection (MiniCPM4 report,
arXiv:2506.07900), plain `jax.numpy`: the definition, differentiable, no
kernel.

Grouped-query attention, G K/V heads each read by H / G query heads. The
selection has no parameters of its own:

  1. compressed keys: the mean of k over every whole window of `kernel`
     tokens at stride `stride`;
  2. a query scores the compressed keys it can see whole (window end <= its
     own position) by softmax(q . ck / sqrt(D)), and its K/V head sums the
     scores of its group's query heads;
  3. a block of `block` tokens takes the largest score among the
     compressed keys whose window overlaps it;
  4. the query attends, causally, to the first `init_blocks` blocks, to the
     blocks its last `window` positions touch, and to the `topk`
     best-scoring other blocks at or before its own.

The choice is discrete and shared by the group: no gradient flows through
it, only through the softmax attention over the chosen tokens. This
function turns the chosen blocks into a (T, T) token mask a K/V head and
runs masked softmax attention under it: memory O(T^2), which is what tests
at small sizes and contexts a little past `dense_len` need. A kernel that
walks the chosen blocks belongs to the PR that brings a cell with long
contexts (PERF.md section 7).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["BlockSelect", "select_blocks", "block_sparse_attention"]


@dataclasses.dataclass(frozen=True)
class BlockSelect:
    """The selection's sizes; the defaults are the `minicpm4` family's."""
    kernel: int = 32        # tokens a compressed key averages
    stride: int = 16        # between the starts of two windows
    block: int = 64         # tokens a selected block holds
    topk: int = 64          # selected blocks beside the forced ones
    init_blocks: int = 1    # leading blocks every query attends to
    window: int = 2048      # trailing positions every query attends to
    dense_len: int = 8192   # up to this length attention stays dense

    def __post_init__(self):
        if self.kernel % self.stride or self.block % self.stride:
            raise ValueError("kernel and block are multiples of stride: "
                             f"{self}")


def select_blocks(q, k, sel):
    """(B, G, T, n_blocks) bool: the blocks each query's group attends to.
    q (B, T, H, D), k (B, T, G, D)."""
    from .flash_attention import _prec
    B, T, H, D = q.shape
    G = k.shape[2]
    n_blocks = -(-T // sel.block)
    n_win = max((T - sel.kernel) // sel.stride + 1, 0)
    pos = jnp.arange(T)
    own = pos // sel.block                          # each query's own block
    blocks = jnp.arange(n_blocks)
    forced = (blocks[None, :] < sel.init_blocks) | \
        (blocks[None, :] >= ((pos - sel.window + 1) // sel.block)[:, None])
    reach = blocks[None, :] <= own[:, None]
    if n_win:
        f32 = jnp.float32
        starts = jnp.arange(n_win) * sel.stride
        gather = starts[:, None] + jnp.arange(sel.kernel)[None, :]
        ck = jnp.mean(k.astype(f32)[:, gather], axis=2)     # (B, W, G, D)
        qg = q.astype(f32).reshape(B, T, G, H // G, D)
        s = jnp.einsum("btgrd,bwgd->bgrtw", qg, ck,
                       precision=_prec(f32)) / math.sqrt(D)
        seen = (starts + sel.kernel - 1)[None, :] <= pos[:, None]  # (T, W)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        p = jnp.sum(jnp.where(seen, p, 0.0), axis=2)        # (B, G, T, W)
        # the windows that overlap block b: the block / stride that start
        # inside it and the kernel / stride - 1 before those
        per, lead = sel.block // sel.stride, sel.kernel // sel.stride - 1
        idx = blocks[:, None] * per - lead + jnp.arange(per + lead)[None, :]
        inside = (idx >= 0) & (idx < n_win)
        score = jnp.max(jnp.where(inside, p[..., jnp.clip(idx, 0, n_win - 1)],
                                  0.0), axis=-1)            # (B, G, T, nb)
    else:
        score = jnp.zeros((B, G, T, n_blocks), jnp.float32)
    score = jnp.where(reach & ~forced, score, -1.0)
    top = min(sel.topk, n_blocks)
    best, at = lax.top_k(score, top)
    chosen = jnp.any((at[..., None] == blocks) & (best[..., None] >= 0.0),
                     axis=-2)
    return chosen | (forced & reach)


def block_sparse_attention(q, k, v, sel, sm_scale=None):
    """Causal grouped-query attention over the selected blocks. q (B, T,
    H, D); k, v (B, T, G, D), query head i reading K/V head i // (H / G).
    Returns (B, T, H, D) in q's dtype."""
    from .flash_attention import _prec
    B, T, H, D = q.shape
    G = k.shape[2]
    scale = 1.0 / math.sqrt(D) if sm_scale is None else sm_scale
    with jax.named_scope("block_select"):
        chosen = select_blocks(lax.stop_gradient(q), lax.stop_gradient(k),
                               sel)
        pos = jnp.arange(T)
        mask = jnp.repeat(chosen, sel.block, axis=-1)[..., :T] & \
            (pos[None, :] <= pos[:, None])                  # (B, G, T, T)
    prec = _prec(q.dtype)
    qg = q.reshape(B, T, G, H // G, D)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k, precision=prec,
                   preferred_element_type=jnp.float32) * scale
    w = jax.nn.softmax(jnp.where(mask[:, :, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", w.astype(v.dtype), v,
                     precision=prec, preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, D).astype(q.dtype)
