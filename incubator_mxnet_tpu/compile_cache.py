"""AOT compilation + persistent executable cache (ROADMAP item 4).

PR 3's `track_jit` made XLA recompiles *observable* at the four choke
points the framework owns (op registry fwd/vjp, fused optimizer dispatch,
kvstore flat-pack, serving executables) — this module makes them
*avoidable*. `cached_jit(key, fn)` is a drop-in replacement for
`track_jit(key, jax.jit(fn))` that routes every call through one shared
two-tier executable cache:

- **memory tier**: a process-wide LRU (`MXNET_EXEC_CACHE_SIZE` entries)
  over AOT-compiled executables, unifying the four ad-hoc caches (serve's
  per-bucket dict that hard-failed when full, the op registry's fwd/vjp
  memo, `optimizer_ops._fused_cache`, kvstore's flat-pack lru_cache) under
  ONE eviction policy;
- **disk tier** (`MXNET_EXEC_CACHE_DIR`, empty = disabled): executables
  are serialized through `jax.experimental.serialize_executable` and keyed
  by a stable content fingerprint, so a *fresh process* deserializes in
  milliseconds instead of re-tracing + re-compiling — a serving fleet
  cold-starts in seconds (PAPERS.md: "Automatic Full Compilation … to
  Cloud TPUs" serialized AOT executables; TVM persisted tuned artifacts
  keyed by shape/dtype).

The fingerprint covers everything that can invalidate an executable:
the traced jaxpr text + closure-captured constants, abstract arg
shapes/dtypes/weak-types and shardings, the jit options (donation), the
cache key, jax version, backend, and device kind/count.  Python's builtin
`hash()` is per-process salted and never used.  A disk entry whose
fingerprint, checksum, or deserialization disagrees is deleted and treated
as a miss — corruption, version skew, or backend mismatch degrade to a
plain recompile, never a crash, never a stale executable.

Telemetry: every lookup reports through `profiler.compile_event` (so the
compile table distinguishes memory hits / disk deserialize-hits / true XLA
retraces), and aggregate `exec_cache_{hits,misses,disk_hits,evictions,
bytes}` counters surface in `profiler.dumps()` and `render_prometheus()`.
A call is two spans in a recorded trace, `mx:exec_lookup` (the call's
signature, the memo, the memory tier, the two locks; arg `kind`) and
`mx:launch` (the loaded executable called); a load or a compile is also a
row of `profiler.setup_stats()`.

This cache is complementary to jax's own persistent *compilation* cache
(`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache` — see
`__init__._configure_jax`): that one
still pays tracing + lowering + cache-key hashing per process; this one
skips straight from abstract shapes to a loaded executable.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
from . import mxsan as _mxsan
import time
from collections import OrderedDict

__all__ = ["cached_jit", "stats", "clear", "disk_stats"]

_MAGIC = b"MXEC1\n"          # on-disk format: MAGIC + fp + "\n" + sha + "\n" + body
_SUFFIX = ".mxec"
_CONST_HASH_BYTES = 1 << 20  # consts larger than this hash by shape/dtype only
_SIG_MEMO_MAX = 512          # per-wrapper signature->fingerprint memo bound

# Module lock guards the LRU + counters (declared in tools/mxlint/lock_order.py).
_lock = _mxsan.lock("compile_cache.py", "_lock")
_mem = OrderedDict()         # fingerprint -> loaded executable (LRU)
_stats = {
    "hits": 0,               # memory-tier hits
    "misses": 0,             # true XLA trace+compile
    "disk_hits": 0,          # fresh-process deserialize instead of compile
    "evictions": 0,          # memory LRU + disk budget evictions
    "bytes": 0,              # disk occupancy (refreshed on writes/scans)
    "disk_errors": 0,        # corrupt/unreadable/unserializable entries
    "fallbacks": 0,          # AOT machinery failed; plain jit served the call
}
_disk_scanned = False        # lazily refresh "bytes" once per process
_warned = set()


# ---------------------------------------------------------------------------
# knobs (registered in util.ENV_VARS; mxlint EV01/EV02 police raw reads)
# ---------------------------------------------------------------------------

def _cache_dir():
    from .util import getenv_str
    d = getenv_str("MXNET_EXEC_CACHE_DIR")
    return os.path.expanduser(d) if d else None


def _mem_cap():
    from .util import getenv_int
    return max(getenv_int("MXNET_EXEC_CACHE_SIZE"), 1)


def _disk_budget():
    from .util import getenv_int
    return getenv_int("MXNET_EXEC_CACHE_DISK_BYTES")


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

def _jax_version():
    import jax
    return str(jax.__version__)


def _backend():
    import jax
    try:
        return jax.default_backend()
    except Exception:       # noqa: BLE001 — no backend yet
        return "unknown"


def _device_kind():
    import jax
    try:
        devs = jax.local_devices()
        return f"{devs[0].device_kind}x{len(devs)}"
    except Exception:       # noqa: BLE001
        return "unknown"


def _default_device():
    import jax
    try:
        return jax.local_devices()[0]
    except Exception:       # noqa: BLE001
        return None


def _leaf_sig(x):
    """Hashable abstract signature of one call-argument leaf."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        # python scalar / bool: jit traces these as weak-typed leaves whose
        # jaxpr is value-independent, so the type alone identifies them
        return ("py", type(x).__name__)
    weak = getattr(x, "weak_type", None)
    if weak is None:
        weak = getattr(getattr(x, "aval", None), "weak_type", False)
    sh = getattr(x, "sharding", None)
    if sh is not None:
        try:
            from jax.sharding import SingleDeviceSharding
            if isinstance(sh, SingleDeviceSharding) and \
                    next(iter(sh.device_set)) == _default_device():
                # an uncommitted array on the default device traces the
                # same as a ShapeDtypeStruct with no sharding: normalize
                # so Predictor.warmup() avals match real-traffic calls
                sh = None
        except Exception:       # noqa: BLE001 — exotic sharding objects
            sh = repr(sh)
    return (tuple(shape), str(dtype), bool(weak), sh)


def _call_sig(args, kwargs):
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(_leaf_sig(x) for x in leaves))


def _fingerprint(key, opts_repr, traced, sig):
    """Stable hex digest identifying one compiled executable across
    processes. sha256 throughout — builtin hash() is per-process salted."""
    import numpy as np
    h = hashlib.sha256()
    for part in ("mxec2", _jax_version(), _backend(), _device_kind(),
                 key, opts_repr, str(sig[0]), repr(sig[1])):
        h.update(part.encode())
        h.update(b"\x00")
    closed = traced.jaxpr
    # the jaxpr text elides closure-captured constant *values*; hash them
    # separately or a changed baked-in table would collide (TS04's hazard)
    # with the name stacks: named scopes reach the executable's metadata
    # (what a device trace is read by) and the plain text prints none
    h.update(closed.pretty_print(name_stack=True).encode())
    for c in getattr(closed, "consts", ()):
        try:
            a = np.asarray(c)
            h.update(repr((tuple(a.shape), str(a.dtype))).encode())
            if a.nbytes <= _CONST_HASH_BYTES:
                h.update(a.tobytes())
        except Exception:       # noqa: BLE001 — non-array consts
            h.update(repr(c).encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# memory tier (process-wide LRU)
# ---------------------------------------------------------------------------

def _mem_get(fp):
    with _lock:
        exe = _mem.get(fp)
        if exe is not None:
            _mem.move_to_end(fp)
    return exe


def _mem_put(fp, exe):
    cap = _mem_cap()
    with _lock:
        _mem[fp] = exe
        _mem.move_to_end(fp)
        while len(_mem) > cap:
            _mem.popitem(last=False)
            _stats["evictions"] += 1


# ---------------------------------------------------------------------------
# disk tier
# ---------------------------------------------------------------------------

def _compile(traced):
    """XLA-compile `traced`. On XLA:CPU a process that has loaded a program
    from jax's persistent cache serializes that program without its
    kernels, even after compiling it again: a fresh process that loads such
    an entry from the disk tier fails at its first run ("Function ... not
    found"). So where the tier will write the executable, on the CPU, jax's
    cache is left out of the compile."""
    lowered = traced.lower()
    if not _cache_dir() or _backend() != "cpu":
        return lowered.compile()
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()


def _entry_path(d, fp):
    return os.path.join(d, fp + _SUFFIX)


def _disk_load(fp):
    """Deserialize one disk entry, or None (missing / corrupt / stale —
    never raises). A bad entry is deleted so it cannot be retried."""
    d = _cache_dir()
    if not d:
        return None
    path = _entry_path(d, fp)
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return None             # plain miss: no entry
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
        payload, in_tree, out_tree, device_ids = pickle.loads(body)
        import jax
        from jax.experimental import serialize_executable as _se
        # load onto the devices the executable was compiled for: the
        # default is EVERY local device, which turns a one-device
        # executable into an N-shard one on any multi-device host
        by_id = {d.id: d for d in jax.local_devices()}
        return _se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in device_ids])
    except Exception as exc:    # noqa: BLE001 — corruption/skew degrade
        with _lock:
            _stats["disk_errors"] += 1
            warn = path not in _warned
            _warned.add(path)
        if warn:
            logging.warning(
                "compile_cache: dropping unusable disk entry %s (%s); "
                "recompiling", path, exc)
        try:
            os.remove(path)
        except OSError:
            pass
        return None


def _disk_store(fp, exe):
    """Best-effort serialize + atomic publish (os.replace): two processes
    racing on the same key each write a private tmp file and the last
    rename wins — readers only ever see a complete entry."""
    d = _cache_dir()
    if not d:
        return False
    try:
        from jax.experimental import serialize_executable as _se
        payload, in_tree, out_tree = _se.serialize(exe)
        device_ids = [d.id for d in
                      exe.runtime_executable().local_devices()]
        body = pickle.dumps((payload, in_tree, out_tree, device_ids))
    except Exception:           # noqa: BLE001 — e.g. host callbacks
        with _lock:
            _stats["disk_errors"] += 1
        return False
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
    _enforce_disk_budget(d)
    return True


def _scan_dir(d):
    """[(path, mtime, size)] of cache entries, oldest first."""
    entries = []
    try:
        names = os.listdir(d)
    except OSError:
        return entries
    for name in names:
        if not name.endswith(_SUFFIX):
            continue
        path = os.path.join(d, name)
        try:
            st = os.stat(path)
        except OSError:
            continue
        entries.append((path, st.st_mtime, st.st_size))
    entries.sort(key=lambda e: e[1])
    return entries


def _enforce_disk_budget(d):
    """Evict oldest entries while occupancy exceeds
    MXNET_EXEC_CACHE_DISK_BYTES (<=0 disables the bound)."""
    global _disk_scanned
    budget = _disk_budget()
    entries = _scan_dir(d)
    total = sum(size for _, _, size in entries)
    evicted = 0
    if budget > 0:
        for path, _mtime, size in entries:
            if total <= budget:
                break
            try:
                os.remove(path)
            except OSError:
                continue
            total -= size
            evicted += 1
    with _lock:
        _stats["bytes"] = total
        _stats["evictions"] += evicted
        _disk_scanned = True


# ---------------------------------------------------------------------------
# the wrapper
# ---------------------------------------------------------------------------

class _CachedJit:
    """Callable wrapping `jax.jit(fn, **jit_kwargs)` behind the two-tier
    executable cache. Signature-compatible with what `track_jit` returned
    (`__wrapped__`, `_compile_key`), plus `.warmup()` for AOT pre-warming.
    """

    def __init__(self, key, fn, **jit_kwargs):
        import jax
        from . import profiler as _prof
        self._key = key
        self._compile_key = key
        self._fn = fn
        self.__wrapped__ = fn
        self._jfn = jax.jit(fn, **jit_kwargs)
        self._opts = repr(sorted(jit_kwargs.items()))
        self._donate = tuple(jit_kwargs.get("donate_argnums", ()) or ())
        # plain-jit escape hatch: anything the AOT path cannot serve
        # (tracer args, exotic leaves, executable/aval skew) runs here,
        # keeping track_jit's probe-based accounting for those calls
        self._fallback = _prof.track_jit(key, self._jfn)
        self._lock = _mxsan.lock(
            "compile_cache.py", "self._lock")           # guards _fps memo
        self._compile_lock = _mxsan.lock(
            "compile_cache.py", "self._compile_lock")   # single-flight compiles
        self._fps = OrderedDict()               # call sig -> fingerprint

    # -- internals ------------------------------------------------------
    def _fingerprint_for(self, args, kwargs):
        """(fingerprint, traced-or-None) for one call signature."""
        sig = _call_sig(args, kwargs)
        with self._lock:
            fp = self._fps.get(sig)
        if fp is not None:
            return fp, None
        traced = self._jfn.trace(*args, **kwargs)
        # shardlint graph capture: this branch runs once per call
        # signature per process, so the observation is free when off and
        # a single snapshot when on
        from . import shardlint as _sl
        if _sl.enabled():
            _sl.record_jit(self._key, traced=traced,
                           donate_argnums=self._donate)
        fp = _fingerprint(self._key, self._opts, traced, sig)
        with self._lock:
            while len(self._fps) >= _SIG_MEMO_MAX:
                self._fps.popitem(last=False)
            self._fps[sig] = fp
        return fp, traced

    def _ensure(self, args, kwargs):
        """Executable for this call signature: (exe, kind, ms) where kind
        is "hit" (memory), "disk" (deserialized), or "miss" (XLA
        compiled). Tracing for the fingerprint is shared with compiling —
        a cold call traces exactly once."""
        fp, traced = self._fingerprint_for(args, kwargs)
        exe = _mem_get(fp)
        if exe is not None:
            with _lock:
                _stats["hits"] += 1
            return exe, "hit", 0.0
        with self._compile_lock:
            exe = _mem_get(fp)
            if exe is not None:
                with _lock:
                    _stats["hits"] += 1
                return exe, "hit", 0.0
            t0 = time.perf_counter()
            exe = _disk_load(fp)
            if exe is not None:
                _mem_put(fp, exe)
                with _lock:
                    _stats["disk_hits"] += 1
                self._note_cost(exe)
                return exe, "disk", (time.perf_counter() - t0) * 1e3
            if traced is None:
                traced = self._jfn.trace(*args, **kwargs)
            exe = _compile(traced)
            ms = (time.perf_counter() - t0) * 1e3
            with _lock:
                _stats["misses"] += 1
            _mem_put(fp, exe)
            _disk_store(fp, exe)
            self._note_cost(exe)
            return exe, "miss", ms

    def _note_cost(self, exe):
        """Compiler cost accounting: record cost_analysis() /
        memory_analysis() for every executable this cache acquires (fresh
        compile or disk deserialize) into the profiler's per-key cost
        table. Gated on the attribution flag like every automatic
        observability hook — otherwise every op a process ever compiles
        leaks into dumps() (callers who want cost unconditionally use
        profiler.cost_from_executable directly).
        Never raises — cost extraction is advisory."""
        try:
            from . import profiler as _prof
            if not _prof.attribution_enabled():
                return
            _prof.cost_from_executable(self._key, exe)
        except Exception:       # noqa: BLE001 — torn-down interpreter
            pass

    def _note_lookup(self, kind, ms, began):
        """One lookup's telemetry: the compile table, and for a load or a
        compile (never a steady call) a row of the set-up table from
        `began` (time.time()), the fingerprint's trace included."""
        from . import profiler as _prof
        _prof.compile_event(self._key, cache_hit=(kind != "miss"),
                            compile_ms=ms, disk=(kind == "disk"))
        if kind != "hit":
            _prof.setup_row("exec_lookup", f"{kind}:{self._key}", began,
                            time.time())

    def _note_fallback(self):
        with _lock:
            _stats["fallbacks"] += 1
            warn = self._key not in _warned
            _warned.add(self._key)
        if warn:
            logging.info(
                "compile_cache: key %r served by plain jit fallback "
                "(argument signature outside the AOT path)", self._key)

    # -- public surface -------------------------------------------------
    def __call__(self, *args, **kwargs):
        from . import profiler as _prof
        # trace-only spans: inside TrainStep's booked `compute`
        with _prof.span("exec_lookup", book=False) as lookup:
            began = time.time()
            try:
                exe, kind, ms = self._ensure(args, kwargs)
            except Exception:       # noqa: BLE001 — tracers/odd leaves
                exe = None
            else:
                lookup.set_metadata(kind=kind)
                self._note_lookup(kind, ms, began)
        if exe is None:
            self._note_fallback()
            return self._fallback(*args, **kwargs)
        try:
            with _prof.span("launch", book=False):
                return exe(*args, **kwargs)
        except Exception:       # noqa: BLE001 — aval/layout skew at call
            self._note_fallback()
            return self._fallback(*args, **kwargs)

    def trace_signature(self, *args, **kwargs):
        """Trace (but do NOT compile) this call signature, returning its
        fingerprint. Cheap way to materialize the jaxpr for one signature
        — the shardlint offline corpus uses it to feed the capture hook
        without paying an XLA compile. Args may be concrete arrays or
        `jax.ShapeDtypeStruct` avals."""
        fp, _traced = self._fingerprint_for(args, kwargs)
        return fp

    def warmup(self, *args, **kwargs):
        """Materialize the executable for this signature WITHOUT running
        it: args may be concrete arrays or `jax.ShapeDtypeStruct` avals.
        Returns "hit" / "disk" / "miss" — a warm fleet sees "disk"."""
        began = time.time()
        exe, kind, ms = self._ensure(args, kwargs)
        del exe
        self._note_lookup(kind, ms, began)
        return kind

    def __repr__(self):
        return f"cached_jit({self._key!r})"


def cached_jit(key, fn, **jit_kwargs):
    """Wrap `fn` as a jitted callable served from the two-tier executable
    cache, reporting per-call hit/disk-hit/retrace telemetry under `key`
    (same key namespace as `profiler.track_jit`)."""
    return _CachedJit(key, fn, **jit_kwargs)


# ---------------------------------------------------------------------------
# introspection / management
# ---------------------------------------------------------------------------

def stats():
    """Aggregate counter snapshot (the `exec_cache_*` telemetry surface):
    hits, misses, disk_hits, evictions, bytes (disk occupancy),
    disk_errors, fallbacks, mem_entries."""
    d = _cache_dir()
    if d and not _disk_scanned:
        disk_stats()            # refresh "bytes" once for warm processes
    with _lock:
        snap = dict(_stats)
        snap["mem_entries"] = len(_mem)
    return snap


def disk_stats():
    """Occupancy snapshot of the disk tier: {dir, entries, bytes, budget}.
    Also refreshes the `bytes` aggregate counter."""
    global _disk_scanned
    d = _cache_dir()
    if not d:
        return {"dir": None, "entries": 0, "bytes": 0,
                "budget": _disk_budget()}
    entries = _scan_dir(d)
    total = sum(size for _, _, size in entries)
    with _lock:
        _stats["bytes"] = total
        _disk_scanned = True
    return {"dir": d, "entries": len(entries), "bytes": total,
            "budget": _disk_budget()}


def clear(memory=True, disk=False, stats=False):
    """Drop cache state. `memory=True` empties the in-process LRU (what a
    fresh replica looks like — tests use it to simulate a cold boot
    against a warm disk tier); `disk=True` deletes the on-disk entries;
    `stats=True` zeroes the counters. Per-wrapper signature memos survive:
    fingerprints are pure functions of the call signature."""
    global _disk_scanned
    if memory:
        with _lock:
            _mem.clear()
    if disk:
        d = _cache_dir()
        if d:
            for path, _mtime, _size in _scan_dir(d):
                try:
                    os.remove(path)
                except OSError:
                    pass
        with _lock:
            _stats["bytes"] = 0
    if stats:
        with _lock:
            for k in _stats:
                _stats[k] = 0
            _disk_scanned = False
            _warned.clear()
