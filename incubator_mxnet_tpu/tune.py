"""Kernel autotuner with a persistent per-shape winner store.

The reference answered "which kernel implementation wins on THIS shape?"
with cudnn_tune=fastest: time every cuDNN algo once per shape at first
forward, remember the winner (src/operator/nn/convolution.cu
CuDNNConvolutionOp::SelectAlgo).  The TPU analog is this module: a
hand-written Pallas kernel is never *assumed* faster than XLA — for every
registered kernel family the tuner times a small search space of
block/tile configs AGAINST the plain-XLA composition and dispatches
whatever measured fastest for the exact ``(kernel, shape, dtype,
device_kind)``.  The "just use XLA" candidate is always in the space, so
a Pallas kernel that loses (see parallel/conv_backward.py's measured
round-4 loss) is unreachable by construction.

Search discipline
-----------------
``tuned_call(kernel, fallback, *args, **kwargs)`` is called from inside
traced op bodies, where the args are tracers and host timing is
impossible.  The tuner therefore searches with SYNTHETIC inputs built
from the (static) aval shapes/dtypes at trace time — the same move XLA's
own conv autotuner makes during compilation.  Winners are keyed on
shape/dtype, so a synthetic search is exactly representative.  Searches
happen at most once per fingerprint per process; the winner is baked
into the jaxpr the outer trace produces, and compile_cache's fingerprint
covers the jaxpr, so a different winner yields a different executable.

Persistence
-----------
Winners live next to PR 6's executables in the ``MXNET_EXEC_CACHE_DIR``
disk tier (subdirectory ``tuned/``), one self-identifying checksummed
MXTN1 file per fingerprint, published atomically (private tmp +
os.replace).  Any corruption, version skew, or stale search-space
version degrades to a re-tune, never an error.  A warm process re-loads
winners from disk and performs ZERO searches.

MXLINT_LOCK_ORDER: see tools/mxlint/lock_order.py ("tune.py").
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import threading
from . import mxsan as _mxsan
import time
from collections import OrderedDict

__all__ = ["register_kernel", "tuned_call", "winner_for", "winners",
           "stats", "clear", "KernelSpec", "xla_only"]

_MAGIC = b"MXTN1\n"   # on-disk: MAGIC + fp + "\n" + sha256(body) + "\n" + body
_SUFFIX = ".mxtn"
_SUBDIR = "tuned"     # under MXNET_EXEC_CACHE_DIR, beside the .mxec blobs

_lock = _mxsan.lock("tune.py", "_lock")
_kernels = {}        # kernel name -> KernelSpec
_winners = {}        # fingerprint -> record dict
_stats = {
    "searches": 0,       # candidate sweeps actually timed (or trivially won)
    "hits": 0,           # memory-table winner lookups served
    "disk_hits": 0,      # winners re-loaded from the persistent store
    "disk_errors": 0,    # corrupt/stale/unwritable winner files
    "fallbacks": 0,      # tuner off / unregistered kernel / winner vanished
    "withheld": 0,       # dispatches inside an xla_only() scope
    # why candidates did not win, counted apart: a kernel the compiler
    # refuses must never look like one that merely lost the race
    "cand_errors": 0,    # candidate (or its builder) raised
    "cand_mismatches": 0,  # ran, but diverged from the XLA reference
    "cand_lost": 0,      # ran and matched, slower than the winner
}


class KernelSpec:
    """One tunable kernel family.

    ``builder(args, kwargs)`` returns an OrderedDict of candidate name ->
    callable for the call signature (reading only static ``.shape`` /
    ``.dtype`` off the args — it runs on tracers), EXCLUDING the implicit
    "xla" candidate, which is always the call-site fallback.  An empty
    dict means "nothing beats XLA here, don't even time it".

    ``bench(fn, *args, **kwargs)`` optionally overrides what one timed
    repetition runs — conv3x3's backward-only kernel times a full
    fwd+bwd ``jax.vjp`` sweep, since its forward is identical to XLA's.

    ``version`` is the search-space version: bump it when the candidate
    set or the kernels themselves change meaningfully, and every
    persisted winner for the family re-tunes (fresh fingerprints).
    """

    def __init__(self, name, builder, *, version=1, bench=None):
        self.name = name
        self.builder = builder
        self.version = version
        self.bench = bench


_scope = threading.local()
_withheld_why = set()       # reasons already logged (guarded by _lock)


@contextlib.contextmanager
def xla_only(reason):
    """Scope (per thread) in which every tuned_call takes its XLA
    candidate without racing, counted under ``withheld``.

    For traces whose program spans several devices: a Mosaic kernel
    cannot be partitioned automatically (jax refuses at lowering, "wrap
    the call in a shard_map"), and tuned_call does not know how its
    operands are sharded, so it cannot wrap the winner itself. The race
    would also run on ONE device at the GLOBAL shape and say nothing
    about the per-shard program."""
    prev = getattr(_scope, "xla_only", None)
    _scope.xla_only = reason
    try:
        yield
    finally:
        _scope.xla_only = prev


def register_kernel(name, builder, *, version=1, bench=None):
    """Register (or replace) a tunable kernel family."""
    spec = KernelSpec(name, builder, version=version, bench=bench)
    with _lock:
        _kernels[name] = spec
    return spec


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def _enabled():
    from .util import getenv_bool
    return getenv_bool("MXNET_TUNE")


def _samples():
    from .util import getenv_int
    return max(getenv_int("MXNET_TUNE_SAMPLES"), 1)


def _tune_dir():
    """Winner-store directory: the ``tuned/`` area of the shared
    MXNET_EXEC_CACHE_DIR disk tier, or None when the tier is off."""
    from .compile_cache import _cache_dir
    d = _cache_dir()
    return os.path.join(d, _SUBDIR) if d else None


# ---------------------------------------------------------------------------
# fingerprinting (same discipline as compile_cache: backend identity in,
# corruption out)
# ---------------------------------------------------------------------------

def _call_key(args, kwargs):
    """Hashable static signature of one call: per-leaf (shape, dtype) for
    array-likes (concrete arrays AND tracers), repr for static leaves.
    kwargs are assumed static configuration, not arrays."""
    parts = []
    for a in args:
        if a is None:
            parts.append("none")
        elif hasattr(a, "shape") and hasattr(a, "dtype"):
            parts.append(f"{tuple(a.shape)}:{str(a.dtype)}")
        else:
            parts.append(repr(a))
    for k in sorted(kwargs):
        parts.append(f"{k}={kwargs[k]!r}")
    return "|".join(parts)


def _fingerprint(kernel, version, call_key):
    from .compile_cache import _backend, _device_kind, _jax_version
    h = hashlib.sha256()
    for part in ("mxtn1", _jax_version(), _backend(), _device_kind(),
                 kernel, str(version), call_key):
        h.update(part.encode("utf-8"))
        h.update(b"\x00")
    return h.hexdigest()


# ---------------------------------------------------------------------------
# persistent winner store
# ---------------------------------------------------------------------------

def _entry_path(d, fp):
    return os.path.join(d, fp + _SUFFIX)


def _disk_load(fp, spec):
    """One winner record from disk, or None (missing/corrupt/stale — a
    bad file is deleted so it re-tunes instead of being retried)."""
    d = _tune_dir()
    if not d:
        return None
    path = _entry_path(d, fp)
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return None             # plain miss
    try:
        if not raw.startswith(_MAGIC):
            raise ValueError("bad magic")
        off = len(_MAGIC)
        stored_fp = raw[off:off + 64].decode("ascii")
        sha = raw[off + 65:off + 129].decode("ascii")
        body = raw[off + 130:]
        if stored_fp != fp:
            raise ValueError("fingerprint mismatch")
        if hashlib.sha256(body).hexdigest() != sha:
            raise ValueError("checksum mismatch")
        rec = json.loads(body.decode("utf-8"))
        if rec.get("kernel") != spec.name:
            raise ValueError("kernel mismatch")
        if rec.get("space_version") != spec.version:
            raise ValueError("stale search-space version")
        if not isinstance(rec.get("winner"), str):
            raise ValueError("no winner recorded")
        if not isinstance(rec.get("rejected"), dict):
            raise ValueError("record predates rejection reasons")
        return rec
    except Exception as exc:    # noqa: BLE001 — corruption degrades
        with _lock:
            _stats["disk_errors"] += 1
        logging.warning("tune: dropping unusable winner file %s (%s); "
                        "re-tuning", path, exc)
        try:
            os.remove(path)
        except OSError:
            pass
        return None


def _disk_store(fp, rec):
    """Atomic best-effort publish (private tmp + os.replace), mirroring
    compile_cache._disk_store: racing writers each finish a private file
    and the last rename wins; readers never see a torn entry."""
    d = _tune_dir()
    if not d:
        return False
    body = json.dumps(rec, sort_keys=True).encode("utf-8")
    blob = (_MAGIC + fp.encode("ascii") + b"\n"
            + hashlib.sha256(body).hexdigest().encode("ascii") + b"\n" + body)
    path = _entry_path(d, fp)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        os.makedirs(d, exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except OSError:
        with _lock:
            _stats["disk_errors"] += 1
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False
    return True


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def _is_traced(x):
    import jax
    return isinstance(x, jax.core.Tracer)


def _concretize(args):
    """Concrete stand-ins for a call signature: tracers are replaced by
    deterministic random arrays of the same shape/dtype, generated on
    the device (winners are keyed on shape/dtype, so synthetic data is
    exactly representative); concrete leaves pass through."""
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(0)
    out = []
    for i, a in enumerate(args):
        if a is None or not _is_traced(a):
            out.append(a)
        elif jnp.issubdtype(a.dtype, jnp.floating):
            out.append(jax.random.normal(jax.random.fold_in(key, i),
                                         tuple(a.shape), a.dtype))
        else:
            out.append(jnp.zeros(tuple(a.shape), a.dtype))
    return tuple(out)


def _tree_close(got, want):
    import jax
    import jax.numpy as jnp
    import numpy as np
    g_leaves, g_tree = jax.tree_util.tree_flatten(got)
    w_leaves, w_tree = jax.tree_util.tree_flatten(want)
    if g_tree != w_tree:
        return False
    for g, w in zip(g_leaves, w_leaves):
        g = np.asarray(g, dtype=np.float64) if hasattr(g, "dtype") else g
        w_arr = np.asarray(w, dtype=np.float64)
        tol = 3e-2 if jnp.asarray(w).dtype == jnp.bfloat16 else 1e-4
        if not np.allclose(g, w_arr, rtol=tol, atol=tol):
            return False
    return True


def _time_one(bench, fn, args, kwargs, samples):
    """(best-of-N wall micros, last result). First call is the untimed
    compile/warmup."""
    import jax
    out = bench(fn, *args, **kwargs)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(samples):
        t0 = time.perf_counter()
        out = bench(fn, *args, **kwargs)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best, out


def _default_bench(fn, *args, **kwargs):
    return fn(*args, **kwargs)


def _reason(exc):
    """`Type: first line` of an exception — what a winner record keeps of
    a candidate the compiler or the runtime refused."""
    lines = str(exc).strip().splitlines()
    return f"{type(exc).__name__}: {lines[0] if lines else ''}"[:300]


def _search(spec, fallback, args, kwargs, fp, call_key):
    """Time every candidate against the XLA fallback on concrete inputs
    and publish the winner (memory + disk). A candidate that raises or
    diverges numerically is disqualified, and the record says which and
    why: ``rejected`` maps its name to ``error: <Type: first line>`` or
    ``mismatch: ...``."""
    import jax
    from .compile_cache import _backend, _device_kind, _jax_version
    rec = {
        "kernel": spec.name,
        "key": call_key,
        "space_version": spec.version,
        "backend": _backend(),
        "device_kind": _device_kind(),
        "jax_version": _jax_version(),
        "winner": "xla",
        "timings_us": {},
        "rejected": {},
    }
    errors = mismatches = 0
    try:
        cands = spec.builder(args, kwargs) or {}
    except Exception as exc:    # noqa: BLE001 — a broken builder: XLA wins
        logging.warning("tune: builder for %s raised (%s); XLA wins",
                        spec.name, _reason(exc))
        rec["rejected"]["<builder>"] = "error: " + _reason(exc)
        errors += 1
        cands = {}
    if cands:
        bench = spec.bench or _default_bench
        samples = _samples()
        # tuned_call sits inside traced op bodies: without stepping out to
        # the eval trace the "concrete" timing runs would be staged into
        # the enclosing trace and nothing would execute. (Not
        # ensure_compile_time_eval: that constant-folds inside the
        # candidates' own traces too, which Pallas kernels do not survive.)
        with jax.core.eval_context():
            cargs = _concretize(args)
            t_ref, ref = _time_one(bench, fallback, cargs, kwargs, samples)
            rec["timings_us"]["xla"] = round(t_ref, 3)
            best_t = t_ref
            for name, fn in cands.items():
                try:
                    t, out = _time_one(bench, fn, cargs, kwargs, samples)
                except Exception as exc:    # noqa: BLE001 — disqualify
                    logging.warning("tune: candidate %s:%s raised (%s)",
                                    spec.name, name, _reason(exc))
                    rec["rejected"][name] = "error: " + _reason(exc)
                    errors += 1
                    continue
                if not _tree_close(out, ref):
                    rec["rejected"][name] = \
                        "mismatch: diverges from the xla reference"
                    mismatches += 1
                    continue
                rec["timings_us"][name] = round(t, 3)
                if t < best_t:
                    best_t = t
                    rec["winner"] = name
    lost = sum(n not in ("xla", rec["winner"]) for n in rec["timings_us"])
    with _lock:
        _stats["searches"] += 1
        _stats["cand_errors"] += errors
        _stats["cand_mismatches"] += mismatches
        _stats["cand_lost"] += lost
        _winners[fp] = rec
    _disk_store(fp, rec)
    return rec


def _lookup(fp, spec):
    """Winner record for a fingerprint, memory first, then the persistent
    store; None means a search is needed."""
    with _lock:
        rec = _winners.get(fp)
        if rec is not None:
            _stats["hits"] += 1
            return rec
    rec = _disk_load(fp, spec)
    if rec is not None:
        with _lock:
            _stats["disk_hits"] += 1
            _winners[fp] = rec
    return rec


# ---------------------------------------------------------------------------
# public surface
# ---------------------------------------------------------------------------

def tuned_call(kernel, fallback, *args, **kwargs):
    """Dispatch ``(*args, **kwargs)`` to the tuned winner for `kernel`,
    searching first if this (shape, dtype, device) was never timed.
    `fallback` is the always-available plain-XLA composition — it IS the
    implicit "xla" candidate, the numerical reference candidates must
    match, and the dispatch target whenever the tuner is off or the
    winner cannot be resolved."""
    with _lock:
        spec = _kernels.get(kernel)
    if spec is None or not _enabled():
        with _lock:
            _stats["fallbacks"] += 1
        return fallback(*args, **kwargs)
    reason = getattr(_scope, "xla_only", None)
    if reason is not None:
        with _lock:
            _stats["withheld"] += 1
            first = reason not in _withheld_why
            _withheld_why.add(reason)
        if first:
            logging.warning("tune: Pallas candidates withheld (%s); "
                            "tuned_call sites take XLA", reason)
        return fallback(*args, **kwargs)
    call_key = _call_key(args, kwargs)
    # shardlint graph capture: metadata only — args may be tracers here,
    # so nothing value-dependent is recorded
    from . import shardlint as _sl
    if _sl.enabled():
        _sl.record_tuned(kernel, call_key)
    fp = _fingerprint(kernel, spec.version, call_key)
    rec = _lookup(fp, spec)
    if rec is None:
        rec = _search(spec, fallback, args, kwargs, fp, call_key)
    name = rec["winner"]
    if name == "xla":
        return fallback(*args, **kwargs)
    fn = (spec.builder(args, kwargs) or {}).get(name)
    if fn is None:
        # persisted winner no longer offered (env gate flipped, candidate
        # set changed without a version bump): degrade to XLA, counted
        with _lock:
            _stats["fallbacks"] += 1
        return fallback(*args, **kwargs)
    return fn(*args, **kwargs)


def winner_for(kernel, *args, **kwargs):
    """Winner name for a call signature WITHOUT searching ("xla",
    a candidate name, or None when never tuned). Read-only: consults the
    memory table and the persistent store."""
    with _lock:
        spec = _kernels.get(kernel)
    if spec is None:
        return None
    fp = _fingerprint(kernel, spec.version, _call_key(args, kwargs))
    rec = _lookup(fp, spec)
    return rec["winner"] if rec is not None else None


def winners():
    """Snapshot of every winner record this process knows (memory table
    plus any disk entries not yet loaded) — the diagnose.py surface."""
    with _lock:
        out = {fp: dict(rec) for fp, rec in _winners.items()}
        specs = dict(_kernels)
    d = _tune_dir()
    if d:
        try:
            names = os.listdir(d)
        except OSError:
            names = []
        for nm in names:
            if not nm.endswith(_SUFFIX):
                continue
            fp = nm[:-len(_SUFFIX)]
            if fp in out:
                continue
            for spec in specs.values():
                rec = _disk_load(fp, spec)
                if rec is not None:
                    out[fp] = rec
                    break
    return out


def stats():
    """Counter snapshot (profiler.dumps() / /metrics surface)."""
    with _lock:
        snap = dict(_stats)
        snap["winners"] = len(_winners)
    return snap


def clear(memory=True, disk=False, stats=False):
    """Drop tuner state: the in-memory winner table, optionally the
    persistent store and/or the counters (mirrors compile_cache.clear)."""
    with _lock:
        if memory:
            _winners.clear()
        if stats:
            for k in _stats:
                _stats[k] = 0
    if disk:
        d = _tune_dir()
        if d:
            try:
                names = os.listdir(d)
            except OSError:
                names = []
            for nm in names:
                if nm.endswith(_SUFFIX):
                    try:
                        os.remove(os.path.join(d, nm))
                    except OSError:
                        pass
