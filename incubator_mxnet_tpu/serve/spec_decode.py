"""Speculative decoding: draft-propose / batched-verify over the paged
KV-cache.

Plain continuous decode (serve/decode.py) pays ONE target-model
dispatch per emitted token. Speculative decoding buys tokens in bulk: a
cheap DRAFT proposes ``k`` tokens per stream per iteration, and exactly
ONE fixed-shape batched VERIFY executable scores all ``k+1`` positions
of every active slot in a single target-model step. The acceptance rule
keeps the longest prefix of the draft that agrees with the target's own
picks, then appends the target's next token (the "bonus"), so emitted
streams are IDENTICAL to plain decode — under greedy sampling,
bit-identical by construction, because every emitted token is the
target's argmax given an accepted (hence correct) context.

The verify executable extends PR 13's zero-retrace contract to a block
of ``G = k+1`` query tokens per slot:

* its shape is fixed at construction (``slots`` x ``G``) — per-stream
  speculation depth varies at runtime by PADDING rows to position -1,
  never by retracing;
* padding/idle rows write out-of-bounds (scatter ``mode="drop"``) and
  read a clamped one-key window, exactly like the decode executable's
  idle slots;
* the attention read path is ``paged_attention_multiquery`` — one
  shared gather of a sequence's pages serves all G queries
  (parallel/paged_attention.py), so verify costs one pass over the KV
  history, not G.

Acceptance rule (greedy). For a slot whose pending token is ``t0`` at
write position ``p0`` with draft ``d1..dk``: verify row ``g`` carries
token ``[t0, d1, .., dk][g]`` at position ``p0+g`` and attends over
positions ``0..p0+g``; its argmax ``y[g]`` is therefore the target's
next token AFTER consuming that row. Accept ``d_j`` iff
``d_j == y[j-1]`` and all earlier drafts were accepted; with ``m``
accepted, emit ``d1..dm`` then ``y[m]`` — m+1 tokens, each provably the
token plain greedy decode would have emitted. KV rows written for
rejected drafts are never read: every later read window is re-covered
by that step's own scatter of verified tokens first.

Page-rollback invariant. Speculation never claims pages: admission
already claimed every page a stream can EVER touch (decode.py), spec
write positions are clamped to the stream's owned capacity
(``k_s <= owned_rows - 1 - p0``), and shared prefix-cache pages hold
only positions below the prompt length, which speculative writes never
reach (any shared tail page was CoW-forked at admission). Rejection
rolls back the draft state and the slot position — page ownership is
untouched — so cancel/drain still returns the allocator to
``live == 0`` with zero leaked pages.

The draft here is SELF-DRAFTING: a host-side numpy replica of the
target's single-layer attention math (same params, float32), so the
measured accept rate is near 1.0 and the speedup bound is the dispatch
amortization (one device step per m+1 tokens). A real deployment plugs
a smaller model in via ``draft_factory``; the acceptance rule does not
depend on draft quality for CORRECTNESS, only for speed.

Adaptive k: each stream carries an EMA of its accept fraction; below
``MXNET_SPEC_ACCEPT_FLOOR_PCT`` the per-stream depth shrinks toward 1
(a bad draft degrades to plain decode cost, never below), and at
sustained near-full acceptance it regrows toward ``MXNET_SPEC_K``.

Lock hierarchy (tools/mxlint/lock_order.py): ``self._compile_lock``
guards verify-executable construction only; draft state is touched
exclusively by the scheduler loop thread and needs no lock.
"""
from __future__ import annotations

import math
import threading

import numpy as _np

from ..base import MXNetError
from .. import util
from .. import mxsan as _mxsan

__all__ = ["DraftState", "SpecDecoder"]

# EMA weight for the per-stream accept-rate estimate: 0.5 reacts within
# a couple of iterations, which matters because a stream only lives for
# max_new_tokens of them
_EMA_ALPHA = 0.5
# accept fraction at/above which adaptive k regrows toward the cap
_GROW_AT = 0.9


class DraftState:
    """Host-side numpy draft model for one stream (self-drafting).

    Replicates the target's single-layer attention LM in float32 numpy:
    a dense per-stream K/V history (``rows`` token rows, (H, D) each)
    stands in for the paged pool, ``propose`` runs the same
    embed -> qkv -> causal attention -> argmax math the device executes.
    Draft K/V never touches the device and never touches the paged pool
    — rejection rollback is a truncate of these arrays, nothing else.

    Invariant between iterations: ``rows == p0`` where ``p0`` is the
    slot's pending write position, i.e. the history holds exactly the
    tokens whose KV the target has COMMITTED (prompt + accepted tokens),
    not the pending token itself.
    """

    def __init__(self, params, num_heads, head_dim, prompt):
        self._p = params
        self._h = int(num_heads)
        self._d = int(head_dim)
        self._scale = 1.0 / math.sqrt(self._d)
        h = params["emb"][_np.asarray(prompt, _np.int64)]      # (n, E)
        self._K = (h @ params["wk"]).reshape(-1, self._h, self._d)
        self._V = (h @ params["wv"]).reshape(-1, self._h, self._d)

    @property
    def rows(self):
        return len(self._K)

    def _append_row(self, token):
        h = self._p["emb"][int(token)]                          # (E,)
        self._K = _np.concatenate(
            [self._K, (h @ self._p["wk"]).reshape(1, self._h, self._d)])
        self._V = _np.concatenate(
            [self._V, (h @ self._p["wv"]).reshape(1, self._h, self._d)])
        return h

    def _advance(self, token):
        """Append ``token``'s KV row and return the draft's greedy next
        token — the same attend-over-0..pos window the target uses."""
        p = self._p
        h = self._append_row(token)
        q = (h @ p["wq"]).reshape(self._h, self._d) * self._scale
        s = _np.einsum("hd,thd->ht", q, self._K)                # (H, T)
        s = s - s.max(axis=-1, keepdims=True)
        w = _np.exp(s)
        w /= w.sum(axis=-1, keepdims=True)
        a = _np.einsum("ht,thd->hd", w, self._V).reshape(-1)
        o = a @ p["wo"] + h
        return int(_np.argmax(o @ p["w_out"]))

    def propose(self, last_token, k):
        """Draft ``k`` tokens continuing from the pending ``last_token``
        (appends k rows: last_token and the first k-1 drafts)."""
        out = []
        t = int(last_token)
        for _ in range(int(k)):
            t = self._advance(t)
            out.append(t)
        return out

    def sync(self, base, written):
        """Reconcile with the verify outcome: ``written`` are the tokens
        now COMMITTED at positions ``base..base+len(written)-1`` (the
        pending token plus the accepted drafts). Rows proposed beyond
        them are rolled back; rows not yet computed (full acceptance,
        zero-k steps) are appended."""
        target = int(base) + len(written)
        if self.rows > target:
            self._K = self._K[:target]
            self._V = self._V[:target]
        while self.rows < target:
            self._append_row(written[self.rows - int(base)])


class SpecDecoder:
    """The verify executable + draft factory + adaptive-k policy for one
    DecodePredictor's geometry.

    ONE fixed-shape verify executable per (slots, G, geometry) — key
    ``serve:verify[s<slots>,g<G>,<geom>]`` in the two-tier compile
    cache, AOT-warmable like the decode executable so a warm boot
    deserializes it from disk with zero compiles.
    """

    def __init__(self, predictor, *, k=None, adapt=None,
                 accept_floor_pct=None, draft_factory=None):
        self.predictor = predictor
        self.k = int(k if k is not None
                     else util.getenv_int("MXNET_SPEC_K"))
        if self.k < 1:
            raise MXNetError(f"MXNET_SPEC_K={self.k}: need >= 1")
        self.width = self.k + 1          # G: pending token + k drafts
        self.adapt = bool(adapt if adapt is not None
                          else util.getenv_bool("MXNET_SPEC_ADAPT"))
        floor = int(accept_floor_pct if accept_floor_pct is not None
                    else util.getenv_int("MXNET_SPEC_ACCEPT_FLOOR_PCT"))
        self.accept_floor = min(max(floor, 0), 100) / 100.0
        self._draft_factory = draft_factory
        self._params_np = {name: _np.asarray(v, _np.float32)
                           for name, v in predictor._param_vals.items()}
        self._compile_lock = _mxsan.lock(
            "serve/spec_decode.py", "self._compile_lock")
        self._verify_fn = None
        self._warm = False

    # -- draft ----------------------------------------------------------
    def make_draft(self, prompt):
        """Fresh per-stream draft state seeded with the prompt's KV."""
        if self._draft_factory is not None:
            return self._draft_factory(prompt)
        return DraftState(self._params_np, self.predictor.num_heads,
                          self.predictor.head_dim, prompt)

    # -- adaptive k -----------------------------------------------------
    def next_k(self, cur_k, ema):
        """Per-stream depth policy: shrink toward 1 below the accept
        floor, regrow toward the cap at sustained near-full acceptance,
        hold in between (hysteresis against oscillation)."""
        if not self.adapt or ema is None:
            return cur_k
        if ema < self.accept_floor:
            return max(1, cur_k - 1)
        if ema >= max(self.accept_floor, _GROW_AT):
            return min(self.k, cur_k + 1)
        return cur_k

    # -- the verify executable ------------------------------------------
    def _verify_key(self):
        p = self.predictor
        return (f"serve:verify[s{p.slots},g{self.width},"
                f"{p._geom_tag()}]")

    def _make_verify(self):
        p = self.predictor
        h_, d_, ps, p_, s_ = (p.num_heads, p.head_dim, p.page_size,
                              p.num_pages, p.slots)
        g_ = self.width
        e_ = p.embed

        def call(params, tokens, positions, k_pages, v_pages, page_tables):
            # tokens (S, G) int32 — row 0 the slot's pending token, rows
            # 1..k its drafts; positions (S, G) int32 write positions,
            # -1 = padding/idle row (write dropped, read clamped, output
            # ignored). Returns y (S, G): the target's greedy next token
            # after each row.
            import jax.numpy as jnp
            from ..parallel.paged_attention import paged_attention_multiquery
            active = positions >= 0
            pos = jnp.maximum(positions, 0)
            h = params["emb"][tokens]                       # (S, G, E)
            q = (h @ params["wq"]).reshape(s_, g_, h_, d_)
            k = (h @ params["wk"]).reshape(s_, g_, h_, d_)
            v = (h @ params["wv"]).reshape(s_, g_, h_, d_)
            row = jnp.arange(s_, dtype=jnp.int32)[:, None]
            flat = page_tables[row, pos // ps] * ps + pos % ps
            flat = jnp.where(active, flat, p_ * ps).reshape(s_ * g_)
            kp = k_pages.reshape(p_ * ps, h_, d_).at[flat].set(
                k.reshape(s_ * g_, h_, d_),
                mode="drop").reshape(p_, ps, h_, d_)
            vp = v_pages.reshape(p_ * ps, h_, d_).at[flat].set(
                v.reshape(s_ * g_, h_, d_),
                mode="drop").reshape(p_, ps, h_, d_)
            attn = paged_attention_multiquery(q, kp, vp, page_tables,
                                              pos + 1)
            o = attn.reshape(s_, g_, e_) @ params["wo"] + h
            logits = o @ params["w_out"]                    # (S, G, V)
            y = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return y, kp, vp

        return call

    def _exec_verify(self):
        with self._compile_lock:
            if self._verify_fn is None:
                from .. import compile_cache as _cc
                self._verify_fn = _cc.cached_jit(self._verify_key(),
                                                 self._make_verify())
        return self._verify_fn

    def warmup(self):
        """AOT-compile THE verify executable. Returns {"verify": kind}
        with kind in {"hit", "disk", "miss"} — a warm boot against a
        populated MXNET_EXEC_CACHE_DIR reports no "miss"."""
        import jax
        import jax.numpy as jnp
        p = self.predictor
        i32 = jnp.int32
        kv = jax.ShapeDtypeStruct((p.num_pages, p.page_size, p.num_heads,
                                   p.head_dim), jnp.float32)
        sg = jax.ShapeDtypeStruct((p.slots, self.width), i32)
        fn = self._exec_verify()
        kind = fn.warmup(
            p._param_vals, sg, sg, kv, kv,
            jax.ShapeDtypeStruct((p.slots, p.max_pages_per_seq), i32))
        self._warm = True
        return {"verify": kind}

    @property
    def is_warm(self):
        return self._warm

    # -- runtime entry point (called by the scheduler loop) -------------
    def verify(self, tokens, positions, k_pages, v_pages, page_tables,
               traces=()):
        """One batched verify dispatch over all slots x G rows.

        ``traces`` optionally carries the reqtrace contexts of the streams
        riding this batch; when non-empty each gets a ``spec_verify`` span
        covering the shared dispatch (same wall interval, per-request id).
        """
        import time as _time

        import jax.numpy as jnp
        fn = self._exec_verify()
        t0 = _time.perf_counter()
        y, kp, vp = fn(self.predictor._param_vals,
                       jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(positions, jnp.int32),
                       k_pages, v_pages,
                       jnp.asarray(page_tables, jnp.int32))
        out = _np.asarray(y)
        if traces:
            from . import reqtrace as _rt
            dur_ms = (_time.perf_counter() - t0) * 1e3
            for ctx in traces:
                _rt.observe(ctx, "spec_verify", dur_ms, t0=t0,
                            args={"width": self.width,
                                  "batch": int(len(out))})
        self._warm = True
        return out, kp, vp
