"""Continuous-batching autoregressive decode over a paged KV-cache.

Predict-only serving (batcher.py) batches at REQUEST granularity: a
batch runs to completion before the next one forms. Autoregressive
decode would waste most of that batch — sequences finish at different
lengths, and a request-level batch holds every slot hostage to its
longest member. This module schedules at ITERATION granularity instead
(the continuous-batching discipline of Orca/vLLM, applied here on the
Ragged-Paged-Attention TPU layout, arXiv:2604.15464): every decode step
first RETIRES finished sequences and ADMITS waiting ones into the freed
slots, so the fixed-shape decode executable stays full under load.

Three pieces:

``PageAllocator``
    Free-list allocator over a fixed pool of KV pages. Sequences own
    whole pages (``page_size`` token rows each); admit pops page ids
    off the free list, retire pushes them back — ZERO data copies in
    either direction, because the pages themselves never move: only the
    per-sequence page table (the indirection paged attention reads through)
    changes.

``DecodePredictor``
    Owns the decode-side executables in the two-tier compile cache:
    one PREFILL executable per prompt-length bucket (the Predictor
    ladder discipline, keys ``serve:prefill[...]``) and exactly ONE
    fixed-shape DECODE executable over the padded slot batch (key
    ``serve:decode[...]``). Idle slots ride along with position -1 and
    their KV writes dropped via out-of-bounds scatter, so steady-state
    decode does ZERO retraces regardless of which sequences come and go.

``DecodeScheduler``
    The iteration-level loop: bounded admission queue (Overloaded shed
    when full, when paused for drain/rollout, or when the projected
    queue wait breaches ``MXNET_DECODE_QUEUE_BOUND_MS`` — the PR-10
    queue-wait-histogram admission signal), per-step
    ``fault.inject("decode")`` chaos hook, and pause/resume/quiesce
    mirroring DynamicBatcher so the PR-12 control plane drains decode
    exactly like predict.

Lock hierarchy (declared in tools/mxlint/lock_order.py): scheduler
``self._lock`` is outermost and guards queue + slot tables only — never
held across device calls; predictor ``self._compile_lock`` guards
executable construction; allocator ``self._alloc_lock`` is a leaf.
The KV pool device arrays are touched ONLY by the scheduler loop
thread, so they need no lock at all.
"""
from __future__ import annotations

import math
import queue
import threading
import time
from collections import deque

import numpy as _np

from ..base import MXNetError
from .. import util
from . import reqtrace as _rt
from .batcher import DeadlineExceeded, Overloaded
from .predictor import BucketLadder
from .stats import ServingStats
from .. import mxsan as _mxsan

__all__ = ["PageAllocator", "DecodePredictor", "DecodeScheduler",
           "DecodeStream"]

_EOS = object()


class PageAllocator:
    """Refcounted free-list allocator over ``num_pages`` KV pages.

    O(1) alloc/free of page IDS only; the backing (P, page_size, H, D)
    pool arrays are owned by the scheduler and never reshaped or
    compacted. Exhaustion raises the retryable ``Overloaded`` (the
    caller either sheds 503 or leaves the request queued); freeing a
    page that is not live raises — a double free here would silently
    corrupt another sequence's context, so it must be loud.

    Pages carry a refcount for the prefix cache (serve/prefix_cache.py):
    ``alloc`` grants exclusive pages (refcount 1), ``share`` adds a
    holder to an already-live page (a cache hit costs no copy), ``free``
    drops one hold and only returns the page to the free list when the
    LAST holder lets go. ``fork`` is the copy-on-write claim: the first
    divergent WRITE to a shared page trades the caller's hold for a
    fresh exclusive page (the caller copies the rows); an exclusive page
    forks to itself, so the unshared fast path stays zero-copy.
    """

    def __init__(self, num_pages):
        if num_pages < 1:
            raise MXNetError("PageAllocator needs at least one page")
        self.num_pages = int(num_pages)
        self._alloc_lock = _mxsan.lock("serve/decode.py", "self._alloc_lock")
        # pop() takes from the tail: keep low page ids first for
        # readable tests, recency-reuse for cache locality in practice
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._live_set = set()
        self._refs = {}
        self.high_water = 0

    def alloc(self, n):
        """Pop `n` page ids; all-or-nothing (no partial grants)."""
        n = int(n)
        if n < 1:
            raise MXNetError(f"alloc({n}): need at least one page")
        with self._alloc_lock:
            if n > len(self._free):
                raise Overloaded(
                    f"KV page pool exhausted: want {n} pages, "
                    f"{len(self._free)}/{self.num_pages} free")
            pages = [self._free.pop() for _ in range(n)]
            self._live_set.update(pages)
            for p in pages:
                self._refs[p] = 1
            self.high_water = max(self.high_water, len(self._live_set))
        return pages

    def share(self, pages):
        """Add one hold per page; pages must already be live (sharing a
        dead page would alias the free list)."""
        with self._alloc_lock:
            for p in pages:
                if p not in self._live_set:
                    raise MXNetError(f"share of non-live KV page {p}")
            for p in pages:
                self._refs[p] += 1
        return pages

    def fork(self, page):
        """Copy-on-write claim before the first divergent write to
        ``page``. Returns ``(page_to_write, copied)``: the same page
        with ``copied=False`` when the caller is the only holder, else
        a fresh exclusive page (caller's hold on the original released)
        with ``copied=True`` — the CALLER copies the row data, this
        class only moves ids. May raise Overloaded when no free page
        remains to back the copy."""
        with self._alloc_lock:
            if page not in self._live_set:
                raise MXNetError(f"fork of non-live KV page {page}")
            if self._refs[page] == 1:
                return page, False
            if not self._free:
                raise Overloaded(
                    f"KV page pool exhausted: no free page to fork "
                    f"shared page {page}")
            fresh = self._free.pop()
            self._live_set.add(fresh)
            self._refs[fresh] = 1
            self._refs[page] -= 1
            self.high_water = max(self.high_water, len(self._live_set))
        return fresh, True

    def free(self, pages):
        with self._alloc_lock:
            for p in pages:
                if p not in self._live_set:
                    raise MXNetError(f"double free of KV page {p}")
            for p in pages:
                self._refs[p] -= 1
                if self._refs[p] == 0:
                    del self._refs[p]
                    self._live_set.remove(p)
                    self._free.append(p)

    def refcount(self, page):
        """Current holder count (0 for a free page)."""
        with self._alloc_lock:
            return self._refs.get(page, 0)

    @property
    def live(self):
        with self._alloc_lock:
            return len(self._live_set)

    @property
    def free_count(self):
        with self._alloc_lock:
            return len(self._free)

    @property
    def used_count(self):
        with self._alloc_lock:
            return len(self._live_set)

    @property
    def shared_count(self):
        """Pages held by more than one owner (the prefix-cache overlap
        the mxnet_kv_pages_shared gauge reports)."""
        with self._alloc_lock:
            return sum(1 for rc in self._refs.values() if rc >= 2)


class DecodePredictor:
    """Decode-side executables for a single-layer attention LM.

    params (all float32 numpy/jax arrays):
      emb (V, E) | wq, wk, wv, wo (E, E) | w_out (E, V), with
      E = num_heads * head_dim. One pre-norm-free attention block plus
      a residual and an output projection — deliberately small, but it
      exercises every serving-side mechanism (paged KV scatter, ragged
      attention reads, greedy sampling) the full model would.

    Geometry (page_size/num_pages/max_pages_per_seq/slots) lives here
    because the DECODE EXECUTABLE'S SHAPE bakes it in: changing any of
    it is a recompile, so it is constructor state, not a runtime knob.
    Prompts are padded up a `prompt_buckets` ladder exactly like
    Predictor; generation is greedy argmax, which makes every stream's
    token sequence a pure function of its prompt — the property the
    continuous-vs-sequential bit-identity test relies on.
    """

    def __init__(self, params, *, num_heads, head_dim, vocab,
                 prompt_buckets=(4, 8, 16), page_size=None, num_pages=None,
                 max_pages_per_seq=None, slots=None):
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.vocab = int(vocab)
        self.embed = self.num_heads * self.head_dim
        self.page_size = int(page_size if page_size is not None
                             else util.getenv_int("MXNET_KV_PAGE_SIZE"))
        self.num_pages = int(num_pages if num_pages is not None
                             else util.getenv_int("MXNET_KV_PAGES"))
        self.max_pages_per_seq = int(
            max_pages_per_seq if max_pages_per_seq is not None
            else util.getenv_int("MXNET_KV_PAGES_PER_SEQ"))
        self.slots = int(slots if slots is not None
                         else util.getenv_int("MXNET_DECODE_SLOTS"))
        if self.page_size < 1 or self.num_pages < 1 or self.slots < 1:
            raise MXNetError("decode geometry must be positive")
        if self.max_pages_per_seq > self.num_pages:
            raise MXNetError("MXNET_KV_PAGES_PER_SEQ exceeds MXNET_KV_PAGES")
        self.ladder = BucketLadder(prompt_buckets)
        exp = {"emb": (self.vocab, self.embed),
               "wq": (self.embed, self.embed),
               "wk": (self.embed, self.embed),
               "wv": (self.embed, self.embed),
               "wo": (self.embed, self.embed),
               "w_out": (self.embed, self.vocab)}
        for name, shape in exp.items():
            if name not in params:
                raise MXNetError(f"param {name} missing (need {sorted(exp)})")
            got = tuple(params[name].shape)
            if got != shape:
                raise MXNetError(f"param {name}: shape {got} != {shape}")
        import jax.numpy as jnp
        self._param_vals = {k: jnp.asarray(v, jnp.float32)
                            for k, v in params.items()}
        self._compile_lock = _mxsan.lock(
            "serve/decode.py", "self._compile_lock")
        self._prefill_fns = {}
        self._decode_fn = None
        self._warm_keys = set()

    @classmethod
    def toy(cls, seed=0, *, vocab=32, num_heads=2, head_dim=8, **kw):
        """Deterministically-initialized small model (tests/bench)."""
        rng = _np.random.RandomState(seed)
        e = num_heads * head_dim

        def w(*shape, s=0.3):
            return (rng.standard_normal(shape) * s).astype(_np.float32)

        params = {"emb": w(vocab, e, s=0.5), "wq": w(e, e), "wk": w(e, e),
                  "wv": w(e, e), "wo": w(e, e), "w_out": w(e, vocab)}
        return cls(params, num_heads=num_heads, head_dim=head_dim,
                   vocab=vocab, **kw)

    # -- geometry helpers ----------------------------------------------
    def pages_for(self, prompt_len, max_new_tokens):
        """Pages a stream owns for its whole life (allocated up front at
        admission — continuous batching never reallocates mid-flight)."""
        return max(1, math.ceil((prompt_len + max_new_tokens)
                                / self.page_size))

    # -- traced model fns ----------------------------------------------
    def _make_prefill(self, t_bucket):
        h_, d_, ps, p_ = (self.num_heads, self.head_dim, self.page_size,
                          self.num_pages)
        e_ = self.embed
        scale = 1.0 / math.sqrt(d_)

        def call(params, tokens, n, k_pages, v_pages, ptrow):
            # tokens (1, T) int32; n () int32 TRACED (real prompt len —
            # one executable per bucket, not per length); ptrow
            # (max_pages_per_seq,) int32 page ids for this sequence
            import jax
            import jax.numpy as jnp
            t = t_bucket
            h = params["emb"][tokens[0]]                     # (T, E)
            q = (h @ params["wq"]).reshape(t, h_, d_)
            k = (h @ params["wk"]).reshape(t, h_, d_)
            v = (h @ params["wv"]).reshape(t, h_, d_)
            s = jnp.einsum("qhd,khd->hqk", q * scale, k)
            pos = jnp.arange(t, dtype=jnp.int32)
            mask = (pos[:, None] >= pos[None, :]) & (pos[None, :] < n)
            s = jnp.where(mask[None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, e_)
            o = a @ params["wo"] + h
            logits = o @ params["w_out"]                     # (T, V)
            nxt = jnp.argmax(logits[n - 1], axis=-1).astype(jnp.int32)
            # scatter the prompt's KV rows into the owned pages; padded
            # rows (pos >= n) aim past the pool and mode="drop" discards
            flat = ptrow[pos // ps] * ps + pos % ps
            flat = jnp.where(pos < n, flat, p_ * ps)
            kp = k_pages.reshape(p_ * ps, h_, d_).at[flat].set(
                k, mode="drop").reshape(p_, ps, h_, d_)
            vp = v_pages.reshape(p_ * ps, h_, d_).at[flat].set(
                v, mode="drop").reshape(p_, ps, h_, d_)
            return nxt, kp, vp

        return call

    def _make_decode(self):
        h_, d_, ps, p_, s_ = (self.num_heads, self.head_dim, self.page_size,
                              self.num_pages, self.slots)
        e_ = self.embed

        def call(params, tokens, positions, k_pages, v_pages, page_tables):
            # tokens (S,) int32 — last emitted token per slot;
            # positions (S,) int32 — its KV write position, -1 = idle
            # slot (writes dropped, attention reads page 0 harmlessly
            # and the output row is ignored by the scheduler)
            import jax.numpy as jnp
            from ..parallel.paged_attention import paged_attention
            active = positions >= 0
            pos = jnp.maximum(positions, 0)
            h = params["emb"][tokens]                        # (S, E)
            q = (h @ params["wq"]).reshape(s_, h_, d_)
            k = (h @ params["wk"]).reshape(s_, h_, d_)
            v = (h @ params["wv"]).reshape(s_, h_, d_)
            row = jnp.arange(s_, dtype=jnp.int32)
            flat = page_tables[row, pos // ps] * ps + pos % ps
            flat = jnp.where(active, flat, p_ * ps)
            kp = k_pages.reshape(p_ * ps, h_, d_).at[flat].set(
                k, mode="drop").reshape(p_, ps, h_, d_)
            vp = v_pages.reshape(p_ * ps, h_, d_).at[flat].set(
                v, mode="drop").reshape(p_, ps, h_, d_)
            attn = paged_attention(q, kp, vp, page_tables, pos + 1)
            o = attn.reshape(s_, e_) @ params["wo"] + h
            logits = o @ params["w_out"]                     # (S, V)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, kp, vp

        return call

    # -- executables ----------------------------------------------------
    def _geom_tag(self):
        return (f"p{self.num_pages}x{self.page_size},h{self.num_heads}"
                f"x{self.head_dim},v{self.vocab}")

    def _prefill_key(self, t_bucket):
        return f"serve:prefill[t{t_bucket},{self._geom_tag()}]"

    def _decode_key(self):
        return f"serve:decode[s{self.slots},{self._geom_tag()}]"

    def _exec_prefill(self, t_bucket):
        with self._compile_lock:
            fn = self._prefill_fns.get(t_bucket)
            if fn is None:
                from .. import compile_cache as _cc
                fn = _cc.cached_jit(self._prefill_key(t_bucket),
                                    self._make_prefill(t_bucket))
                self._prefill_fns[t_bucket] = fn
        return fn

    def _exec_decode(self):
        with self._compile_lock:
            if self._decode_fn is None:
                from .. import compile_cache as _cc
                self._decode_fn = _cc.cached_jit(self._decode_key(),
                                                 self._make_decode())
        return self._decode_fn

    def kv_pool(self):
        """Fresh zeroed (P, page_size, H, D) key and value pools."""
        import jax.numpy as jnp
        shape = (self.num_pages, self.page_size, self.num_heads,
                 self.head_dim)
        return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)

    def warmup(self):
        """AOT-compile every prefill bucket and THE decode executable.

        Returns {"prefill:<bucket>": kind, ..., "decode": kind} with
        kind in {"hit", "disk", "miss"} (compile_cache.warmup): a warm
        boot against a populated MXNET_EXEC_CACHE_DIR reports no
        "miss" anywhere, i.e. zero retraces before the first request.
        """
        import jax
        import jax.numpy as jnp
        i32 = jnp.int32
        kv = jax.ShapeDtypeStruct((self.num_pages, self.page_size,
                                   self.num_heads, self.head_dim),
                                  jnp.float32)
        ptrow = jax.ShapeDtypeStruct((self.max_pages_per_seq,), i32)
        out = {}
        for t_bucket in self.ladder.sizes:
            fn = self._exec_prefill(t_bucket)
            out[f"prefill:{t_bucket}"] = fn.warmup(
                self._param_vals,
                jax.ShapeDtypeStruct((1, t_bucket), i32),
                jax.ShapeDtypeStruct((), i32), kv, kv, ptrow)
            self._warm_keys.add(f"prefill:{t_bucket}")
        fn = self._exec_decode()
        out["decode"] = fn.warmup(
            self._param_vals,
            jax.ShapeDtypeStruct((self.slots,), i32),
            jax.ShapeDtypeStruct((self.slots,), i32), kv, kv,
            jax.ShapeDtypeStruct((self.slots, self.max_pages_per_seq), i32))
        self._warm_keys.add("decode")
        return out

    @property
    def is_warm(self):
        want = {f"prefill:{b}" for b in self.ladder.sizes} | {"decode"}
        return want <= self._warm_keys

    # -- runtime entry points (called by the scheduler loop) ------------
    def prefill(self, prompt, k_pages, v_pages, ptrow):
        """Run one prompt; returns (first generated token id, updated
        pools). Raises MXNetError when the prompt exceeds the ladder."""
        import jax.numpy as jnp
        n = len(prompt)
        t_bucket = self.ladder.bucket_for(n)
        if t_bucket is None:
            raise MXNetError(f"prompt length {n} exceeds the prefill "
                             f"ladder {self.ladder.sizes}")
        toks = _np.zeros((1, t_bucket), _np.int32)
        toks[0, :n] = prompt
        fn = self._exec_prefill(t_bucket)
        nxt, kp, vp = fn(self._param_vals, jnp.asarray(toks),
                         jnp.asarray(n, jnp.int32), k_pages, v_pages,
                         jnp.asarray(ptrow, jnp.int32))
        self._warm_keys.add(f"prefill:{t_bucket}")
        return int(nxt), kp, vp

    def decode(self, tokens, positions, k_pages, v_pages, page_tables):
        """One batched decode step over all slots (idle rows pos=-1)."""
        import jax.numpy as jnp
        fn = self._exec_decode()
        nxt, kp, vp = fn(self._param_vals,
                         jnp.asarray(tokens, jnp.int32),
                         jnp.asarray(positions, jnp.int32),
                         k_pages, v_pages,
                         jnp.asarray(page_tables, jnp.int32))
        self._warm_keys.add("decode")
        return _np.asarray(nxt), kp, vp


class DecodeStream:
    """Handle for one in-flight generation: iterate for tokens as they
    land (per-token streaming), or block on result() for the full list.
    The first token arrives from PREFILL (its latency is the TTFT);
    every later token from a decode step."""

    def __init__(self, prompt, max_new_tokens, eos_id, deadline):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.deadline = deadline
        self.submit_t = time.monotonic()
        self.ttft_ms = None
        self._q = queue.Queue()
        self._tokens = []
        self._done = threading.Event()
        self._error = None
        self._cancelled = False
        # scheduler-owned bookkeeping
        self._slot = -1
        self._pages = None
        self._pages_needed = 0
        self._last_t = None
        self._kv_import = None
        # request tracing (serve/reqtrace.py): the router-minted context
        # and the scheduler-measured TTFT budget components
        self._trace = None
        self._budget = None
        # speculative-decode state (spec schedulers only)
        self._draft = None
        self._spec_k = 0
        self._spec_ema = None

    def _deliver(self, tok, now):
        if self.ttft_ms is None:
            self.ttft_ms = (now - self.submit_t) * 1e3
        self._tokens.append(tok)
        self._last_t = now
        self._q.put(tok)

    def _finish(self, error=None):
        self._error = error
        self._done.set()
        self._q.put(_EOS)

    def cancel(self):
        """Ask the scheduler to retire this stream at its next step
        (client went away); already-queued tokens stay readable."""
        self._cancelled = True

    @property
    def done(self):
        return self._done.is_set()

    @property
    def error(self):
        return self._error

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _EOS:
                if self._error is not None:
                    raise self._error
                return
            yield item

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise DeadlineExceeded("stream still running")
        if self._error is not None:
            raise self._error
        return list(self._tokens)


class DecodeScheduler:
    """Iteration-level scheduler: one loop thread interleaves
    retire -> admit -> step so freed slots and freed KV pages are reused
    on the very next iteration (see module docstring)."""

    def __init__(self, predictor, *, stats=None, max_queue=None,
                 max_new_tokens=None, queue_bound_ms=None, name="decode",
                 prefix_cache=None, chunk_prefill=None, spec_decode=None,
                 spec_k=None):
        self.predictor = predictor
        # speculative decoding (serve/spec_decode.py): when enabled the
        # loop's step is draft-propose + ONE batched verify instead of
        # ONE decode dispatch; emitted tokens are bit-identical under
        # greedy, so this is purely a throughput knob
        if spec_decode is None:
            spec_decode = util.getenv_bool("MXNET_SPEC_DECODE")
        self.spec = None
        if spec_decode:
            from .spec_decode import SpecDecoder
            self.spec = SpecDecoder(predictor, k=spec_k)
        self.stats = stats if stats is not None else ServingStats(name)
        self._max_queue = int(max_queue if max_queue is not None
                              else util.getenv_int("MXNET_DECODE_QUEUE"))
        self._default_max_new = int(
            max_new_tokens if max_new_tokens is not None
            else util.getenv_int("MXNET_DECODE_MAX_NEW_TOKENS"))
        self._queue_bound_ms = float(
            queue_bound_ms if queue_bound_ms is not None
            else util.getenv_int("MXNET_DECODE_QUEUE_BOUND_MS"))
        self.allocator = PageAllocator(predictor.num_pages)
        # prefix_cache: True builds a PrefixCache over this scheduler's
        # allocator; or pass an instance already bound to it. Cache hits
        # are completed by CHUNKED suffix prefill (serve/disagg.py), so
        # a chunk executable is built lazily unless chunk_prefill hands
        # in a pre-warmed PrefillPredictor.
        if prefix_cache is True:
            from .prefix_cache import PrefixCache
            prefix_cache = PrefixCache(self.allocator, predictor.page_size)
        self.prefix_cache = prefix_cache
        self._chunk_fn = chunk_prefill
        s = predictor.slots
        self._lock = _mxsan.lock("serve/decode.py", "self._lock")
        self._wake = threading.Event()
        self._waiting = deque()
        self._active = [None] * s
        self._positions = _np.full(s, -1, _np.int32)
        self._tokens = _np.zeros(s, _np.int32)
        self._page_tables = _np.zeros((s, predictor.max_pages_per_seq),
                                      _np.int32)
        self._k_pages = None
        self._v_pages = None
        self._running = False
        self._accepting = True
        self._pause_reason = ""
        self._thread = None
        self.stats.set_gauge("kv_pages_total", predictor.num_pages)

    # -- lifecycle ------------------------------------------------------
    def start(self):
        with self._lock:
            if self._running:
                return self
            self._running = True
        if self._k_pages is None:
            self._k_pages, self._v_pages = self.predictor.kv_pool()
        # AOT-build the fixed-shape batched-verify executable before the
        # loop thread serves traffic, so speculation never retraces
        # mid-stream (same contract as DecodePredictor.warmup: a warm
        # boot against a populated cache dir reports "disk", not "miss").
        if self.spec is not None and not self.spec.is_warm:
            self.spec.warmup()
        self._thread = threading.Thread(target=self._loop,
                                        name="mxtpu-decode", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain=True):
        if drain and self._thread is not None:
            self.pause("stop")
            self.quiesce(timeout=30)
        with self._lock:
            if not self._running:
                return
            self._running = False
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        self._fail_all(MXNetError("decode scheduler stopped"))

    def _fail_all(self, err):
        with self._lock:
            victims = list(self._waiting) + [st for st in self._active
                                             if st is not None]
            self._waiting.clear()
            self._active = [None] * self.predictor.slots
            self._positions[:] = -1
        for st in victims:
            if st._pages:
                self.allocator.free(st._pages)
                st._pages = None
            st._finish(err)
        self._set_pool_gauges()

    # -- admission control (control-plane surface) ----------------------
    def pause(self, reason="pause"):
        with self._lock:
            self._accepting = False
            self._pause_reason = reason

    def resume(self):
        with self._lock:
            self._accepting = True
            self._pause_reason = ""

    @property
    def accepting(self):
        with self._lock:
            return self._accepting

    @property
    def active_streams(self):
        """Streams queued or occupying a slot — the load-report signal
        routers use for decode placement."""
        with self._lock:
            return (len(self._waiting)
                    + sum(1 for st in self._active if st is not None))

    def quiesce(self, timeout=30.0):
        """Wait until no stream is queued or in a slot. Pair with
        pause(): quiescing with admission open may never converge."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                idle = (not self._waiting
                        and all(st is None for st in self._active))
            if idle:
                return True
            self._wake.set()
            time.sleep(0.005)
        return False

    def submit(self, prompt, max_new_tokens=None, eos_id=None,
               deadline_ms=None, kv_import=None, trace=None):
        """Queue one generation; returns a DecodeStream immediately.

        Sheds (Overloaded, 503-retryable) rather than queueing into
        collapse: when paused, when the bounded queue is full, and when
        the PROJECTED queue wait — p95 of recent admission waits scaled
        by the queue depth ahead of this request — breaches
        MXNET_DECODE_QUEUE_BOUND_MS (0 disables). Oversized requests
        (prompt beyond the ladder, page demand beyond the per-sequence
        cap) raise plain MXNetError: retrying those elsewhere cannot
        succeed, so they must not be labelled retryable.

        ``kv_import`` is the disaggregated admission path: a dict with
        ``k_rows``/``v_rows`` ((m, page_size, H, D) float32 rows as
        exported by a prefill replica), ``n`` (prompt length those rows
        cover) and ``next_token`` (the prefill's greedy pick). Admission
        then writes the shipped rows into freshly allocated pages and
        starts decoding at position ``n`` — no local prefill, no
        ladder constraint on the prompt.

        ``trace`` (a reqtrace.RequestTrace, or None) rides the stream so
        admission books ``decode_admission``/``first_step`` spans and
        the TTFT budget components against the request's trace id.
        """
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise MXNetError("empty prompt")
        if not self._running:
            raise MXNetError("decode scheduler not started")
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self._default_max_new)
        if max_new < 1:
            raise MXNetError(f"max_new_tokens={max_new}: need >= 1")
        if kv_import is not None:
            kv_import = self._check_kv_import(kv_import, prompt)
        elif self.predictor.ladder.bucket_for(len(prompt)) is None:
            raise MXNetError(
                f"prompt length {len(prompt)} exceeds the prefill "
                f"ladder {self.predictor.ladder.sizes}")
        pages_needed = self.predictor.pages_for(len(prompt), max_new)
        if pages_needed > self.predictor.max_pages_per_seq:
            raise MXNetError(
                f"request needs {pages_needed} KV pages, per-sequence cap "
                f"is {self.predictor.max_pages_per_seq} "
                f"(MXNET_KV_PAGES_PER_SEQ)")
        deadline = (time.monotonic() + deadline_ms / 1e3
                    if deadline_ms else None)
        with self._lock:
            if not self._accepting:
                self.stats.incr("shed_draining")
                raise Overloaded(
                    f"decode admission paused: {self._pause_reason}")
            if len(self._waiting) >= self._max_queue:
                self.stats.incr("shed_queue_full")
                raise Overloaded(
                    f"decode queue full ({self._max_queue})")
            self._shed_if_projected_wait_locked()
            st = DecodeStream(prompt, max_new, eos_id, deadline)
            st._pages_needed = pages_needed
            st._kv_import = kv_import
            st._trace = trace
            self._waiting.append(st)
            self.stats.incr("requests_total")
            self.stats.incr("decode_streams_total")
            self.stats.set_gauge("queue_depth", len(self._waiting))
        self._wake.set()
        return st

    def _shed_if_projected_wait_locked(self):
        if self._queue_bound_ms <= 0:
            return
        qw = self.stats.queue_wait
        if qw.count < 8:
            return  # no signal yet: admit optimistically
        projected_ms = qw.percentile(95) * 1e3 * (len(self._waiting) + 1)
        if projected_ms > self._queue_bound_ms:
            self.stats.incr("shed_projected")
            raise Overloaded(
                f"projected queue wait {projected_ms:.1f} ms breaches "
                f"MXNET_DECODE_QUEUE_BOUND_MS={self._queue_bound_ms:.0f}")

    def _check_kv_import(self, kv_import, prompt):
        p = self.predictor
        try:
            n = int(kv_import["n"])
            nxt = int(kv_import["next_token"])
            k_rows = _np.asarray(kv_import["k_rows"], _np.float32)
            v_rows = _np.asarray(kv_import["v_rows"], _np.float32)
        except (KeyError, TypeError, ValueError) as e:
            raise MXNetError(f"malformed kv_import: {e}")
        if n != len(prompt):
            raise MXNetError(f"kv_import covers {n} tokens but the "
                             f"prompt has {len(prompt)}")
        m = math.ceil(n / p.page_size)
        row_shape = (m, p.page_size, p.num_heads, p.head_dim)
        for name, rows in (("k_rows", k_rows), ("v_rows", v_rows)):
            if tuple(rows.shape) != row_shape:
                raise MXNetError(
                    f"kv_import {name} shape {tuple(rows.shape)} != "
                    f"{row_shape} for this replica's geometry")
        return {"n": n, "next_token": nxt,
                "k_rows": k_rows, "v_rows": v_rows}

    # -- the loop -------------------------------------------------------
    def _loop(self):
        while True:
            with self._lock:
                if not self._running:
                    return
                busy = (bool(self._waiting)
                        or any(st is not None for st in self._active))
            if not busy:
                self._wake.wait(0.05)
                self._wake.clear()
                continue
            try:
                self._admit()
                self._step()
            except Exception as e:  # noqa: BLE001 — loop must survive
                self.stats.incr("errors")
                self._fail_all(e if isinstance(e, MXNetError)
                               else MXNetError(f"decode step failed: {e}"))
            self.stats.publish()

    def _set_pool_gauges(self):
        live = self.allocator.live
        self.stats.set_gauge("kv_pages_live", live)
        self.stats.set_gauge("kv_page_occupancy",
                             live / self.allocator.num_pages)
        self.stats.set_gauge("kv_pages_free", self.allocator.free_count)
        self.stats.set_gauge("kv_pages_used", live)
        self.stats.set_gauge("kv_pages_shared", self.allocator.shared_count)
        if self.prefix_cache is not None:
            pc = self.prefix_cache.stats()
            self.stats.set_gauge("prefix_cache_hits", pc["hits"])
            self.stats.set_gauge("prefix_cache_misses", pc["misses"])
            self.stats.set_gauge("prefix_tokens_saved", pc["tokens_saved"])
        with self._lock:
            n_active = sum(st is not None for st in self._active)
            depth = len(self._waiting)
        self.stats.set_gauge("decode_active", n_active)
        self.stats.set_gauge("queue_depth", depth)

    def _chunker(self):
        if self._chunk_fn is None:
            from .disagg import PrefillPredictor
            self._chunk_fn = PrefillPredictor(self.predictor)
        return self._chunk_fn

    def _claim_pages_locked(self, st):
        """Build the admission plan for one stream while holding the
        scheduler lock: every page the stream will EVER touch is claimed
        here (exclusive alloc, shared prefix-cache hit, or CoW fork of
        a shared tail), all-or-nothing. Raises Overloaded to hold the
        queue with nothing leaked."""
        if st._kv_import is not None:
            return {"mode": "import",
                    "pages": self.allocator.alloc(st._pages_needed)}
        if self.prefix_cache is None:
            return {"mode": "plain",
                    "pages": self.allocator.alloc(st._pages_needed)}
        pages, covered, partial = self.prefix_cache.lookup(st.prompt)
        cow = None
        try:
            if partial:
                # the suffix prefill writes into the tail page: first
                # divergent write, so take the copy-on-write claim now
                fresh, copied = self.allocator.fork(pages[-1])
                if copied:
                    cow = (pages[-1], fresh)
                pages = pages[:-1] + [fresh]
            extra = st._pages_needed - len(pages)
            if extra > 0:
                pages = pages + self.allocator.alloc(extra)
        except Overloaded:
            if pages:
                self.allocator.free(pages)
            raise
        return {"mode": "cached", "pages": pages, "covered": covered,
                "cow": cow}

    def _admit(self):
        """Move waiting streams into free slots until slots or pages run
        out. Pages are claimed for the stream's WHOLE lifetime up front
        — admission is the only place a stream can block on memory, so
        an admitted stream always runs to completion."""
        while True:
            with self._lock:
                if not self._waiting:
                    return
                free_slots = [i for i, st in enumerate(self._active)
                              if st is None]
                if not free_slots:
                    return
                st = self._waiting[0]
                now = time.monotonic()
                if st.deadline is not None and now > st.deadline:
                    self._waiting.popleft()
                    self.stats.incr("shed_deadline")
                    st._finish(DeadlineExceeded(
                        "deadline expired while queued"))
                    continue
                try:
                    plan = self._claim_pages_locked(st)
                except Overloaded:
                    return  # pool exhausted: hold the queue, a retire
                    # will free pages and the next iteration re-admits
                self._waiting.popleft()
                slot = free_slots[0]
                st._slot = slot
                st._pages = plan["pages"]
                queue_wait = now - st.submit_t
            pages = plan["pages"]
            ptrow = _np.zeros(self.predictor.max_pages_per_seq, _np.int32)
            ptrow[:len(pages)] = pages
            if self.spec is not None:
                # seed the stream's draft with the prompt's KV — works
                # uniformly for plain/cached/import admission because
                # the prompt tokens are always known host-side
                st._draft = self.spec.make_draft(st.prompt)
                st._spec_k = self.spec.k
                st._spec_ema = None
            t0 = time.monotonic()
            nxt, pos = self._run_admission(st, plan, ptrow)
            now = time.monotonic()
            self.stats.queue_wait.observe(queue_wait)
            self.stats.prefill_time.observe(now - t0)
            with self._lock:
                self._page_tables[slot] = ptrow
                self._positions[slot] = pos
                self._tokens[slot] = nxt
                self._active[slot] = st
            st._deliver(nxt, now)
            self.stats.ttft.observe(
                now - st.submit_t,
                trace=st._trace.trace_id if st._trace is not None
                and st._trace.sampled else None)
            if st._trace is not None:
                # scheduler-side TTFT budget: queue wait + admission
                # device work + the residual (bookkeeping, draft seeding,
                # delivery) as first_step; the server's done row merges
                # these with the router-side legs
                ttft_ms = (now - st.submit_t) * 1e3
                queue_ms = queue_wait * 1e3
                admission_ms = (now - t0) * 1e3
                first_step_ms = max(0.0, ttft_ms - queue_ms - admission_ms)
                st._budget = {"queue_ms": round(queue_ms, 3),
                              "admission_ms": round(admission_ms, 3),
                              "first_step_ms": round(first_step_ms, 3)}
                _rt.observe(st._trace, "decode_admission", admission_ms,
                            args={"mode": plan["mode"],
                                  "pages": len(plan["pages"])})
                _rt.observe(st._trace, "first_step", first_step_ms)
            self.stats.incr("decode_tokens_total")
            if (len(st._tokens) >= st.max_new_tokens
                    or nxt == st.eos_id or st._cancelled):
                self._retire(st)
            self._set_pool_gauges()

    def _run_admission(self, st, plan, ptrow):
        """Fill the stream's pages (no scheduler lock held — device
        work). Returns (first token, decode start position)."""
        import jax.numpy as jnp
        if plan["mode"] == "import":
            imp = st._kv_import
            m = len(imp["k_rows"])
            idx = jnp.asarray(plan["pages"][:m])
            self._k_pages = self._k_pages.at[idx].set(
                jnp.asarray(imp["k_rows"]))
            self._v_pages = self._v_pages.at[idx].set(
                jnp.asarray(imp["v_rows"]))
            self.stats.incr("kv_pages_imported_total", m)
            return imp["next_token"], imp["n"]
        if plan["mode"] == "cached":
            if plan["cow"] is not None:
                src, dst = plan["cow"]
                self._k_pages = self._k_pages.at[dst].set(
                    self._k_pages[src])
                self._v_pages = self._v_pages.at[dst].set(
                    self._v_pages[src])
            nxt = self._chunked_prefill(st.prompt, plan["covered"], ptrow)
            self.prefix_cache.insert(st.prompt, list(plan["pages"]),
                                     len(st.prompt))
            return nxt, len(st.prompt)
        nxt, kp, vp = self.predictor.prefill(
            st.prompt, self._k_pages, self._v_pages, ptrow)
        self._k_pages, self._v_pages = kp, vp
        return nxt, len(st.prompt)

    def _chunked_prefill(self, prompt, start, ptrow):
        """Prefill positions start..len(prompt)-1 in fixed chunks,
        interleaving one decode step between chunks whenever slots are
        active — a colocated replica's in-flight streams never wait for
        a whole long prompt."""
        chunker = self._chunker()
        nxt = None
        for lo in range(start, len(prompt), chunker.chunk):
            if lo > start:
                with self._lock:
                    busy = any(s is not None for s in self._active)
                if busy:
                    self._step()
            nxt, kp, vp = chunker.prefill_chunk(
                prompt, lo, self._k_pages, self._v_pages, ptrow)
            self._k_pages, self._v_pages = kp, vp
        return nxt

    def _step(self):
        """One iteration's device work: the speculative draft+verify
        step when spec decode is on, the plain decode dispatch
        otherwise."""
        if self.spec is not None:
            return self._spec_step()
        return self._plain_step()

    def _plain_step(self):
        """One fixed-shape decode dispatch over all slots, then per-slot
        deliver/retire. The chaos hook fires BEFORE the device call so a
        kill lands mid-stream with tokens already flushed to clients."""
        from .. import fault
        with self._lock:
            active = [(i, st) for i, st in enumerate(self._active)
                      if st is not None]
            if not active:
                return
            tokens = self._tokens.copy()
            positions = self._positions.copy()
            page_tables = self._page_tables.copy()
        if _rt.enabled():
            # fault-site breadcrumb carries the active request trace ids
            # so a kill -9 postmortem joins the request trace
            traces = [st._trace.trace_id for _, st in active
                      if st._trace is not None]
            if traces:
                fault.flight_record("decode_step", traces=traces)
        fault.inject("decode")
        t0 = time.monotonic()
        nxt, kp, vp = self.predictor.decode(
            tokens, positions, self._k_pages, self._v_pages, page_tables)
        self._k_pages, self._v_pages = kp, vp
        now = time.monotonic()
        step_s = now - t0
        self.stats.decode_step_time.observe(step_s)
        # PR-10 queue-wait-vs-device signal, bucket = the slot batch
        self.stats.observe_bucket(self.predictor.slots, (), step_s)
        self.stats.incr("batches_total")
        self.stats.set_gauge("batch_occupancy",
                             len(active) / self.predictor.slots)
        for i, st in active:
            tok = int(nxt[i])
            with self._lock:
                self._positions[i] += 1
                self._tokens[i] = tok
            if st.deadline is not None and now > st.deadline:
                self.stats.incr("shed_deadline")
                self._retire(st, DeadlineExceeded(
                    "deadline expired mid-generation"))
                continue
            if st._cancelled:
                self._retire(st)
                continue
            if st._last_t is not None:
                self.stats.token_latency.observe(now - st._last_t)
            st._deliver(tok, now)
            self.stats.incr("decode_tokens_total")
            if (len(st._tokens) >= st.max_new_tokens
                    or tok == st.eos_id):
                self._retire(st)
        self._set_pool_gauges()

    def _spec_step(self):
        """One speculative iteration: host-side draft proposals for
        every active slot, then ONE fixed-shape batched verify dispatch,
        then longest-agreeing-prefix acceptance (see spec_decode.py for
        the rule and why greedy outputs stay bit-identical).

        Per-slot depth ``k_s`` is clamped to (a) the stream's adaptive
        k, (b) ``remaining - 1`` so the m+1 emitted tokens can never
        overshoot max_new_tokens, and (c) the stream's OWNED page
        capacity so a speculative write can never land outside pages
        claimed at admission (ptrow's zero padding would silently alias
        page 0 otherwise). Unused verify rows pad at position -1. The
        chaos hook fires BEFORE the verify dispatch, mirroring
        _plain_step's decode site."""
        from .. import fault
        spec = self.spec
        ps = self.predictor.page_size
        with self._lock:
            active = [(i, st) for i, st in enumerate(self._active)
                      if st is not None]
            if not active:
                return
            base_tokens = self._tokens.copy()
            base_positions = self._positions.copy()
            page_tables = self._page_tables.copy()
        tokens = _np.zeros((self.predictor.slots, spec.width), _np.int32)
        positions = _np.full((self.predictor.slots, spec.width), -1,
                             _np.int32)
        drafts = {}
        t_draft = time.monotonic()
        for i, st in active:
            t0 = int(base_tokens[i])
            p0 = int(base_positions[i])
            remaining = st.max_new_tokens - len(st._tokens)
            owned_cap = len(st._pages) * ps - 1 - p0
            k_s = max(0, min(st._spec_k, remaining - 1, owned_cap))
            d = st._draft.propose(t0, k_s) if k_s > 0 else []
            drafts[i] = d
            tokens[i, 0] = t0
            positions[i, 0] = p0
            for j, dt in enumerate(d):
                tokens[i, j + 1] = dt
                positions[i, j + 1] = p0 + j + 1
        self.stats.spec_draft_time.observe(time.monotonic() - t_draft)
        rt_ctxs = ()
        if _rt.enabled():
            rt_ctxs = [st._trace for _, st in active
                       if st._trace is not None]
            if rt_ctxs:
                # fault-site breadcrumb: the verify kill drill's
                # postmortem joins the request trace by these ids
                fault.flight_record(
                    "spec_verify",
                    traces=[c.trace_id for c in rt_ctxs])
        fault.inject("verify")
        t0v = time.monotonic()
        y, kp, vp = spec.verify(tokens, positions, self._k_pages,
                                self._v_pages, page_tables,
                                traces=rt_ctxs)
        self._k_pages, self._v_pages = kp, vp
        now = time.monotonic()
        step_s = now - t0v
        self.stats.spec_verify_time.observe(step_s)
        self.stats.decode_step_time.observe(step_s)
        self.stats.observe_bucket(self.predictor.slots, (), step_s)
        self.stats.incr("batches_total")
        self.stats.incr("spec_steps_total")
        self.stats.set_gauge("batch_occupancy",
                             len(active) / self.predictor.slots)
        k_live = []
        for i, st in active:
            d = drafts[i]
            k_s = len(d)
            m = 0
            while m < k_s and d[m] == int(y[i, m]):
                m += 1
            emitted = list(d[:m]) + [int(y[i, m])]
            if k_s:
                frac = m / k_s
                self.stats.spec_accept_rate.observe(frac)
                self.stats.incr("spec_tokens_proposed_total", k_s)
                self.stats.incr("spec_tokens_accepted_total", m)
                st._spec_ema = (frac if st._spec_ema is None
                                else (0.5 * frac + 0.5 * st._spec_ema))
                st._spec_k = spec.next_k(st._spec_k, st._spec_ema)
            k_live.append(st._spec_k)
            p0 = int(base_positions[i])
            # rejection rollback: truncate the draft history to the
            # accepted prefix (committed KV positions p0..p0+m); page
            # ownership is untouched — speculation never claims pages
            st._draft.sync(p0, [int(tokens[i, 0])] + list(d[:m]))
            with self._lock:
                self._positions[i] = p0 + m + 1
                self._tokens[i] = emitted[-1]
            if st.deadline is not None and now > st.deadline:
                self.stats.incr("shed_deadline")
                self._retire(st, DeadlineExceeded(
                    "deadline expired mid-generation"))
                continue
            if st._cancelled:
                self._retire(st)
                continue
            finished = False
            for tok in emitted:
                if st._last_t is not None:
                    self.stats.token_latency.observe(now - st._last_t)
                st._deliver(tok, now)
                self.stats.incr("decode_tokens_total")
                if (len(st._tokens) >= st.max_new_tokens
                        or tok == st.eos_id):
                    # plain decode would have stopped HERE: tokens past
                    # the eos are discarded, keeping streams identical
                    finished = True
                    break
            if finished:
                self._retire(st)
        if k_live:
            self.stats.set_gauge("spec_adaptive_k",
                                 sum(k_live) / len(k_live))
        self._set_pool_gauges()

    def _retire(self, st, error=None):
        with self._lock:
            if st._slot >= 0 and self._active[st._slot] is st:
                self._active[st._slot] = None
                self._positions[st._slot] = -1
            pages, st._pages = st._pages, None
        if pages:
            self.allocator.free(pages)
        st._finish(error)
        self.stats.incr("decode_retired_total")
        if error is None:
            self.stats.incr("responses_ok")
        self._wake.set()  # freed slot + pages: re-admit immediately
