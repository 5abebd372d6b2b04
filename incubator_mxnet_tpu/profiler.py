"""Profiler: op-level events + chrome-trace output + aggregate stats +
runtime telemetry (memory profiler, jit-recompile tracker, Prometheus
scrape surface).

Reference: src/profiler/profiler.h:251 (typed stats in per-thread buffers,
chrome://tracing JSON at profiler.h:79,432, DumpProfile:299, aggregate
table aggregate_stats.cc, GPU memory profiler behind profile_memory) and
python/mxnet/profiler.py (set_config / set_state / start / stop / dump /
dumps + scoped markers + Domain/Task/Event/Counter/Marker).

TPU-native redesign: engine-op instrumentation becomes a dispatch hook on
the op registry (the only choke point every eager/compiled call crosses),
and kernel-level detail comes from jax.profiler (XPlane) when a tensorboard
directory is configured. Dispatch is async under XLA — `profile_sync=True`
(the default while profiling) blocks on each op's output so durations are
real compute times, mirroring the reference's GPU stream-sync profiling
mode (profiler.h kSimple vs kAccurate).

What the device trace names. With `set_config(tensorboard_dir=...)` and
`start()`, TensorBoard's trace viewer and op profile show each compiled
operation under the names the program was traced with (its `op_name`):
inside a jitted program every registered op carries its registry name
(`Convolution`, `BatchNorm`, `FusedBNAddReLU`, `Pooling`, `FullyConnected`,
...), `parallel.TrainStep` puts the whole step under `forward`, `loss` and
`optimizer`, and `TransformerLM`'s step adds `embed`, `layer<i>/attn`,
`layer<i>/mlp`, `final_ln` and `logits` under `forward`. jax writes the
pass itself: `jvp(forward)/..` is the forward pass, `transpose(jvp(forward))
/..` the backward pass, and `../checkpoint/rematted_computation/..` the
forward pass recomputed inside it. The flash-attention kernels appear as
`flash_fwd`, `flash_bwd_dq` and `flash_bwd_dkv`, the fused convolution
kernels as `conv_bn_relu_<variant>`, `bn_act`, `bn_add_act` and
`bn_apply`. A fusion is listed under the
scope of the instruction XLA made its root, so a convolution fused with a
BatchNorm epilogue is one `Convolution` entry. Eager dispatch enters no
scope. `perfbench/op_scopes.py` reduces the same names to shares of device
time.

Four telemetry layers beyond the reference:

- **Memory profiler** (`profile_memory=True`): NDArray construction and the
  fused-step donation path report device buffers here; live/peak bytes are
  accounted per device in pure python (finalizers decrement on free) and
  emitted as `ph:"C"` counter tracks in the chrome trace plus a Memory
  section in dumps(). The reference's analog is the GpuDeviceStorageProfiler
  (storage_profiler.h) behind the same config flag.
- **Jit/compile tracker**: every cached-jit choke point the framework owns
  (op registry, fused optimizer dispatch, kvstore flat-pack, serving
  executables) wraps its compiled callable in `track_jit(key, fn)`, which
  detects XLA recompilation per call (via the jit cache size) and records
  it through `compile_event(key, cache_hit, compile_ms)`. A cache key
  recompiling more than MXNET_COMPILE_WARN_THRESHOLD times logs a warning —
  the classic leaked-python-scalar / unbucketed-shape bug.
- **Host spans on the trace's clock, set-up rows**: `span(phase)` is a
  `jax.profiler.TraceAnnotation` named `mx:<phase>` whenever a jax profiler
  session records (its books stay behind MXNET_STEP_ATTRIBUTION), so a
  device trace shows what the host did in each idle gap of the chip; and
  an always-on, bounded table of set-up rows `(phase, name, t0, t1)`
  (jax's trace / lower / build time spans and the program's own builders)
  says where the seconds before the first step go: `setup_stats()`. The
  span names and the row format are set out above `span` and above
  `setup_row`; `perfbench/host_spans.py` reads both.
- **Scrape surface**: `render_prometheus()` serializes the counter/gauge
  registry in Prometheus text exposition format (served at GET /metrics by
  serve/server.py), and `continuous_dump`/`dump_period` run a daemon thread
  writing rolling chrome traces for long training runs.
"""
from __future__ import annotations

import itertools
import json
import logging
import os
import re
import threading
import time
import weakref
from collections import defaultdict

import jax

from .base import MXNetError
from . import mxsan as _mxsan

__all__ = ["set_config", "set_state", "start", "stop", "dump", "dumps",
           "pause", "resume", "is_running", "Scope", "Task", "Event",
           "Counter", "Marker", "Domain", "compile_event", "compile_stats",
           "compile_totals", "track_jit", "memory_event", "memory_stats",
           "memory_enabled", "render_prometheus",
           "span", "observe_phase", "request_phase", "attribution_enabled",
           "attribution_enable", "phase_stats", "phase_step_end",
           "last_step_phases", "span_records", "next_span_id", "trace_id",
           "clock_sync_event", "cost_event", "cost_stats",
           "cost_from_executable", "DEVICE_PEAKS", "device_peaks",
           "mfu_stats", "setup_row", "setup_span", "setup_stats"]

_lock = _mxsan.lock("profiler.py", "_lock")
_state = {
    "running": False,
    "paused": False,
    "filename": "profile.json",
    "aggregate_stats": False,
    "sync": True,
    "tb_dir": None,
    "tb_active": False,
    "profile_memory": False,
    "continuous": False,
    "dump_period": 1.0,
}
# event dicts: {"name","cat","ts","dur","tid","ph"} (+optional "s","args")
_events = []
_counters = []       # (name, ts_us, value) sample series
_counter_last = {}   # name -> latest value (the Prometheus gauge registry)
# rolling (continuous_dump) trims fold into these so dumps() still
# aggregates the whole run while each trace segment stays bounded
_agg_events = {}     # name -> [count, total_us, min_us, max_us]
_agg_counts = {}     # counter name -> folded sample count
_dump_seq = 0        # rolling trace segment number (never reused)


def set_config(filename="profile.json", profile_all=False,
               profile_symbolic=True, profile_imperative=True,
               profile_memory=False, profile_api=False,
               aggregate_stats=False, continuous_dump=False,
               dump_period=1.0, profile_sync=True, tensorboard_dir=None,
               **kwargs):
    """Reference profiler.py set_config / MXSetProcessProfilerConfig."""
    _state["filename"] = filename
    _state["aggregate_stats"] = aggregate_stats
    _state["sync"] = profile_sync
    _state["tb_dir"] = tensorboard_dir
    _state["profile_memory"] = bool(profile_memory)
    _state["continuous"] = bool(continuous_dump)
    _state["dump_period"] = max(float(dump_period), 0.05)


def set_state(state="stop", profile_process="worker"):
    """'run' or 'stop' (reference profiler.py set_state)."""
    if state == "run":
        start()
    elif state == "stop":
        stop()
    else:
        raise MXNetError(f"invalid profiler state {state!r}")


def start(profile_process="worker"):
    from .ops import registry
    _state["running"] = True
    _state["paused"] = False
    # a start() opens a fresh profiling window: compile telemetry gathered
    # before it (the registry records always-on) belongs to the previous
    # window and would pollute this session's dumps()/compile table
    with _clock:
        _compile.clear()
        _compile_warned.clear()
    registry.PROFILER_HOOK = _op_hook
    if _state["profile_memory"]:
        _mem["enabled"] = True
        from .ndarray import ndarray as _ndmod
        _ndmod.MEMORY_HOOK = _note_alloc
    if _state["continuous"]:
        _start_dump_thread()
    if _state["tb_dir"]:
        os.makedirs(_state["tb_dir"], exist_ok=True)
        jax.profiler.start_trace(_state["tb_dir"])
        _state["tb_active"] = True


def stop(profile_process="worker"):
    from .ops import registry
    _state["running"] = False
    registry.PROFILER_HOOK = None
    # uninstall the allocation hook (accounting stays readable in dumps())
    _mem["enabled"] = False
    from .ndarray import ndarray as _ndmod
    _ndmod.MEMORY_HOOK = None
    _stop_dump_thread()
    if _state.get("tb_active"):
        jax.profiler.stop_trace()
        _state["tb_active"] = False


def is_running():
    """True while the profiler is collecting (started and not paused).
    Periodic publishers (Trainer step counters, serving stats) gate their
    Counter.set_value calls on this so an idle profiler doesn't accumulate
    an unbounded counter series."""
    return _state["running"] and not _state["paused"]


def pause(profile_process="worker"):
    _state["paused"] = True


def resume(profile_process="worker"):
    _state["paused"] = False


# ---------------------------------------------------------------------------
# continuous dump (reference profiler.h continuous_dump_: rolling traces so
# a long run that never reaches a clean exit still leaves profile data)
# ---------------------------------------------------------------------------

_dump_thread = None
_dump_stop = threading.Event()


def _start_dump_thread():
    global _dump_thread
    if _dump_thread is not None and _dump_thread.is_alive():
        return
    _dump_stop.clear()

    def _loop():
        while not _dump_stop.wait(_state["dump_period"]):
            if _state["running"]:
                try:
                    dump(finished=False)
                except Exception:       # noqa: BLE001 — never kill the run
                    logging.exception("profiler continuous dump failed")

    _dump_thread = threading.Thread(target=_loop, name="mxtpu-profiler-dump",
                                    daemon=True)
    _dump_thread.start()


def _stop_dump_thread():
    global _dump_thread
    _dump_stop.set()
    t, _dump_thread = _dump_thread, None
    if t is not None and t.is_alive():
        t.join(timeout=5)


# ---------------------------------------------------------------------------
# event recording
# ---------------------------------------------------------------------------

def _op_hook(name, fn, args):
    """Installed into registry.PROFILER_HOOK: time one op dispatch."""
    if not _state["running"] or _state["paused"]:
        return fn(*args)
    t0 = time.perf_counter()
    out = fn(*args)
    if _state["sync"]:
        _block(out)
    dur = (time.perf_counter() - t0) * 1e6
    with _lock:
        _events.append({"name": name, "cat": "operator", "ts": t0 * 1e6,
                        "dur": dur, "tid": threading.get_ident(), "ph": "X"})
    return out


def _block(out):
    if isinstance(out, (tuple, list)):
        for o in out:
            _block(o)
    elif hasattr(out, "block_until_ready"):
        out.block_until_ready()


def _record(name, category, t0_us, dur_us, ph="X", scope=None, args=None):
    ev = {"name": name, "cat": category, "ts": t0_us, "dur": dur_us,
          "tid": threading.get_ident(), "ph": ph}
    if scope is not None:
        ev["s"] = scope
    if args is not None:
        ev["args"] = args
    with _lock:
        _events.append(ev)


def _counter_sample(name, value):
    """Append one sample to the counter series and refresh the last-value
    registry. Callers that need atomic read-modify-write (Counter) hold
    `_lock` already and use `_counter_sample_locked`."""
    with _lock:
        _counter_sample_locked(name, value)


def _counter_sample_locked(name, value):
    _counters.append((name, time.perf_counter() * 1e6, value))
    _counter_last[name] = value


# ---------------------------------------------------------------------------
# jit/compile tracker
# ---------------------------------------------------------------------------

_clock = _mxsan.lock("profiler.py", "_clock")
# key -> [hits, misses, compile_ms_total, last_ms, disk_hits]; disk_hits
# counts the subset of hits served by deserializing a persistent-cache
# entry (compile_cache disk tier) rather than reusing an in-process one
_compile = {}
_compile_warned = set()


def _warn_threshold():
    from .util import getenv_int
    return getenv_int("MXNET_COMPILE_WARN_THRESHOLD")


def compile_event(key, cache_hit, compile_ms=0.0, disk=False):
    """Record one lookup against a compiled-executable cache.

    key:       stable cache identity ("op:dot", "fused:adam_update[n=4]",
               "kvstore:flat_pack[13]", "serve:exec[8x6]", ...)
    cache_hit: True when an already-compiled executable served the call
    compile_ms: trace+compile wall time charged to a miss
    disk:      the hit deserialized a persistent compile_cache entry (a
               fresh process avoiding an XLA retrace) rather than reusing
               an executable already loaded in this process

    Always-on (independent of start/stop): recompile pathologies are
    exactly the thing you need visibility into *before* deciding to
    profile. pause() still suppresses it — pause is the explicit "don't
    record this region" request. A key whose miss count passes
    MXNET_COMPILE_WARN_THRESHOLD logs one warning — the classic
    silent-recompile-per-step bug (leaked python scalar in a param,
    shape bucket miss, donation failure).
    """
    if _state["paused"]:
        return
    warn = None
    with _clock:
        rec = _compile.get(key)
        if rec is None:
            rec = _compile[key] = [0, 0, 0.0, 0.0, 0]
        if cache_hit:
            rec[0] += 1
            if disk:
                rec[4] += 1
        else:
            rec[1] += 1
            rec[2] += float(compile_ms)
            rec[3] = float(compile_ms)
            if rec[1] > _warn_threshold() and key not in _compile_warned:
                _compile_warned.add(key)
                warn = rec[1]
    if warn is not None:
        logging.warning(
            "profiler: cache key %r has compiled %d times "
            "(MXNET_COMPILE_WARN_THRESHOLD=%d) — a python scalar leaking "
            "into a traced program or an unbucketed shape is recompiling "
            "every step", key, warn, _warn_threshold())


def compile_stats():
    """Snapshot {key: {hits, misses, compile_ms, last_compile_ms,
    disk_hits}} (disk_hits <= hits: persistent-cache deserializes)."""
    with _clock:
        return {k: {"hits": v[0], "misses": v[1],
                    "compile_ms": v[2], "last_compile_ms": v[3],
                    "disk_hits": v[4]}
                for k, v in _compile.items()}


def compile_totals():
    """(total_hits, total_misses) over every tracked cache. The Trainer
    diffs the miss total around each step into `recompiles_per_step`."""
    with _clock:
        h = m = 0
        for v in _compile.values():
            h += v[0]
            m += v[1]
        return h, m


def track_jit(key, fn):
    """Wrap a jax.jit-compiled callable so every call records a
    compile_event: a call that grows the executable's internal cache (new
    shape/dtype signature -> XLA retrace+compile) is a miss charged with
    the call's wall time; a steady-state call is a hit.
    """
    probe = fn._cache_size
    state = {"captured": False}
    state_lock = _mxsan.lock("profiler.py", "state_lock")

    def _maybe_capture(args, kwargs):
        # shardlint graph capture for track_jit sites that did not route
        # through cached_jit: re-trace the jitted callable once (analysis
        # mode only — enabled() is off in production)
        from . import shardlint as _sl
        if not _sl.enabled():
            return
        try:
            _sl.record_jit(key, traced=fn.trace(*args, **kwargs))
        except Exception:       # noqa: BLE001 — capture must never break a call
            pass

    def wrapped(*args, **kwargs):
        if not state["captured"]:
            with state_lock:
                first_capture = not state["captured"]
                state["captured"] = True
            if first_capture:
                _maybe_capture(args, kwargs)
        before = probe()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        dt_ms = (time.perf_counter() - t0) * 1e3
        if probe() > before:
            compile_event(key, cache_hit=False, compile_ms=dt_ms)
        else:
            compile_event(key, cache_hit=True)
        return out

    wrapped.__wrapped__ = fn
    wrapped._compile_key = key
    return wrapped


# ---------------------------------------------------------------------------
# step-time attribution (StepTimeline): profiler.span(phase) attributes every
# train step / serve request into named phases — input_wait, h2d, compute,
# collective, optimizer, ckpt_snapshot, queue_wait.
#
# ONE span API, two sinks. (1) The trace's clock, always: while a jax
# profiler session records (the benchmark's traced slice, start() with a
# tensorboard_dir, anyone's jax.profiler.start_trace) a span is a
# jax.profiler.TraceAnnotation named "mx:<phase>" on its thread's line of
# the host plane, on the same clock as the device's "XLA Ops" lines, so an
# idle gap of the chip can be laid against what the host was doing. With no
# session recording that costs one read of TraceMe's flag. (2) The
# aggregates (phase table, histograms, span ids, the chrome-trace event of
# this module's own dump, the step vector of heartbeats): only under
# MXNET_STEP_ATTRIBUTION, the shardlint cached-boolean pattern; off (the
# default) _span_records stays 0 (counter-asserted). Spans whose time an
# enclosing or enclosed booked span already holds are trace-only
# (`book=False`), so the table under the gate is what it was.
#
# Names on the training path, parent to child (perfbench/host_spans.py reads
# them; the names are the contract, as `forward`/`loss`/`optimizer` are for
# the device's scopes):
#   mx:train_step        TrainStep.__call__, whole (trace-only)
#     mx:h2d             _to_device: the eager dtype cast, any device_put
#     mx:rng             the eager key split (trace-only)
#     mx:compute         round the jitted step: lookup + launch (+ the wait
#                        for the loss, under the gate only)
#       mx:exec_lookup   _CachedJit: call signature, memo, memory tier,
#                        compile_event; arg kind = hit | disk | miss
#       mx:launch        _CachedJit: the loaded executable called
#   mx:input_wait        run_epoch's and DevicePrefetcher.__next__'s wait
#   mx:prefetch_place    the prefetcher's worker thread, round its device_put
#   mx:ckpt_snapshot, mx:collective, mx:optimizer, mx:pushpull, mx:server:<op>
#                        the older sites, unchanged
# ---------------------------------------------------------------------------

_Annotation = jax.profiler.TraceAnnotation
_recording = _Annotation.is_enabled     # TraceMe's flag: a session is on

_attr_enabled = None        # cached MXNET_STEP_ATTRIBUTION read
# log-spaced ms histogram bounds shared by every phase (floor 10us, x1.6):
# rendered as mxnet_step_phase_ms Prometheus histograms
_PHASE_BOUNDS = tuple(0.01 * (1.6 ** i) for i in range(30))
# phase -> [count, total_ms, max_ms, last_ms, bucket_counts[len+1]]
_phases = {}
_span_records = 0           # spans actually booked (zero-overhead assert)
_span_seq = 0               # process-wide span-id counter (wire-propagated)
_span_tls = threading.local()   # per-thread active-span stack (nesting)
_trace_id = None            # lazy per-process trace identity
_step_phases_cur = {}       # phase -> ms accumulated in the step in flight
_step_phases_last = {}      # previous step's phase vector (heartbeats)
_step_seq = 0               # steps closed by phase_step_end()


def attribution_enabled():
    """True when step-time attribution is on. The env var is read once
    and cached — the gate sits on the per-batch hot path."""
    global _attr_enabled
    if _attr_enabled is None:
        from .util import getenv_bool
        _attr_enabled = getenv_bool("MXNET_STEP_ATTRIBUTION")
    return _attr_enabled


def attribution_enable(on=True):
    """Force attribution on/off for this process (tests, bench); returns
    the previous effective state."""
    global _attr_enabled
    prev = attribution_enabled()
    _attr_enabled = bool(on)
    return prev


def _reset_phases_locked():
    global _span_records, _step_phases_cur, _step_phases_last, _step_seq
    _phases.clear()
    _span_records = 0
    _step_phases_cur = {}
    _step_phases_last = {}
    _step_seq = 0


def span_records():
    """Spans booked since the last reset. The zero-overhead contract:
    with MXNET_STEP_ATTRIBUTION unset this stays exactly 0 through any
    amount of run_epoch / batcher traffic."""
    with _lock:
        return _span_records


def next_span_id():
    """Process-unique monotonically increasing span id (propagated on the
    kvstore wire so worker push/pull spans link to server handler spans).
    Thread-safe: the increment happens under the module lock."""
    global _span_seq
    with _lock:
        _span_seq += 1
        return _span_seq


def trace_id():
    """Lazy per-process trace identity carried in span args and wire
    headers, so a merged multi-process timeline can attribute every span
    to its origin process."""
    global _trace_id
    if _trace_id is None:
        _trace_id = f"{os.getpid():x}.{int(time.time() * 1e3) & 0xffffffff:x}"
    return _trace_id


def current_span_id():
    """Id of this thread's innermost active span (None outside any span):
    what the kvstore client stamps on outgoing wire frames."""
    stack = getattr(_span_tls, "stack", None)
    return stack[-1][1] if stack else None


class _NullSpan:
    """Shared no-op returned while attribution is off and no profiler
    session records: no allocation, no lock, no counter — the off path
    costs two boolean checks."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kwargs):
        pass


_NULL_SPAN = _NullSpan()


def _annotation(phase, args):
    return _Annotation("mx:" + phase, **args) if args \
        else _Annotation("mx:" + phase)


class _Span:
    __slots__ = ("_phase", "_args", "_t0", "_note", "span_id", "parent_id")

    def __init__(self, phase, args):
        self._phase = phase
        self._args = args
        self._t0 = None
        self._note = None
        self.span_id = None
        self.parent_id = None

    def __enter__(self):
        stack = getattr(_span_tls, "stack", None)
        if stack is None:
            stack = _span_tls.stack = []
        self.parent_id = stack[-1][1] if stack else None
        self.span_id = next_span_id()
        stack.append((self._phase, self.span_id))
        if _recording():
            self._note = _annotation(self._phase, self._args)
            self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        dur_ms = (t1 - self._t0) * 1e3
        if self._note is not None:
            self._note.__exit__(*exc)
        stack = getattr(_span_tls, "stack", None)
        if stack and stack[-1][1] == self.span_id:
            stack.pop()
        _book_phase(self._phase, self._t0, dur_ms,
                    self.span_id, self.parent_id, self._args)
        return False

    def set_metadata(self, **kwargs):
        """Args learned inside the span (a cache lookup's outcome)."""
        self._args = dict(self._args or {}, **kwargs)
        if self._note is not None:
            self._note.set_metadata(**kwargs)


def span(phase, args=None, book=True):
    """Context manager attributing the enclosed wall time to `phase`.

    While a jax profiler session records, the span is a TraceAnnotation
    "mx:<phase>" in that session's trace, whatever the gate says. Under
    MXNET_STEP_ATTRIBUTION it also books per-phase aggregates + histogram
    and (while this profiler is running) a nested chrome-trace X span
    carrying span_id/parent/trace linkage args; `book=False` keeps a span
    out of those books (its time is inside a booked span, or holds one).
    With neither, this returns a shared no-op. The object entered has
    `set_metadata(**kwargs)` for args known only inside the span."""
    if book and attribution_enabled():
        return _Span(str(phase), args)
    if _recording():
        return _annotation(str(phase), args)
    return _NULL_SPAN


def observe_phase(phase, dur_ms, t0=None, args=None):
    """Book an externally MEASURED duration into `phase` — for waits that
    cannot be enclosed in a ``with span(...)`` block, like the serve
    batcher's queue_wait (enqueue happened on another thread). `t0` is a
    time.perf_counter()-base start in seconds (defaults to now − dur)."""
    if not attribution_enabled():
        return
    if t0 is None:
        t0 = time.perf_counter() - dur_ms / 1e3
    _book_phase(str(phase), t0, float(dur_ms), next_span_id(), None, args)


def request_phase(phase, t0, dur_ms, span_id, parent_id, extra):
    """Book one request-scoped span from serve/reqtrace.py regardless of
    the MXNET_STEP_ATTRIBUTION gate — the reqtrace layer runs behind its
    own MXNET_REQTRACE gate and has already decided this record should
    exist. Shares the phase aggregates, span-id sequence, and (while the
    profiler is running) the chrome-trace event buffer, so request spans
    land in the same dump files trace_merge joins."""
    _book_phase(str(phase), t0, float(dur_ms), int(span_id), parent_id,
                extra)


def _phase_bucket(dur_ms):
    for i, b in enumerate(_PHASE_BOUNDS):
        if dur_ms <= b:
            return i
    return len(_PHASE_BOUNDS)


def _book_phase(phase, t0, dur_ms, span_id, parent_id, extra):
    global _span_records
    running = _state["running"] and not _state["paused"]
    ev = None
    if running:
        args = {"span_id": span_id, "trace": trace_id()}
        if parent_id is not None:
            args["parent"] = parent_id
        if extra:
            args.update(extra)
        ev = {"name": f"phase:{phase}", "cat": "step", "ts": t0 * 1e6,
              "dur": dur_ms * 1e3, "tid": threading.get_ident(), "ph": "X",
              "args": args}
    with _lock:
        rec = _phases.get(phase)
        if rec is None:
            rec = _phases[phase] = [0, 0.0, 0.0, 0.0,
                                    [0] * (len(_PHASE_BOUNDS) + 1)]
        rec[0] += 1
        rec[1] += dur_ms
        rec[2] = max(rec[2], dur_ms)
        rec[3] = dur_ms
        rec[4][_phase_bucket(dur_ms)] += 1
        _span_records += 1
        # only top-level spans accumulate into the step vector: a nested
        # sub-span's time is already inside its parent's
        if parent_id is None:
            _step_phases_cur[phase] = _step_phases_cur.get(phase, 0.0) \
                + dur_ms
        if ev is not None:
            _events.append(ev)


def phase_step_end():
    """Close the step in flight: the accumulated top-level phase vector
    becomes last_step_phases() (what heartbeats carry to the server's
    straggler report) and the next step starts clean."""
    if not attribution_enabled():
        return
    global _step_phases_cur, _step_phases_last, _step_seq
    with _lock:
        if _step_phases_cur:
            _step_phases_last = _step_phases_cur
            _step_phases_cur = {}
            _step_seq += 1


def last_step_phases():
    """{phase: ms} vector of the most recently closed step (empty until
    attribution records one)."""
    with _lock:
        return dict(_step_phases_last)


def phase_bounds():
    """Upper bucket bounds (ms) of the attribution histograms — shared
    by the local Prometheus exposition and the fleetobs cross-rank
    aggregation (both sides must agree on the bucket layout)."""
    return _PHASE_BOUNDS


def phase_histograms():
    """{phase: {"count", "sum_ms", "buckets"}} snapshot of the raw
    per-phase histogram counts (cumulative since the last reset; the
    final bucket is the +Inf overflow). What fleetobs ships on the
    heartbeat — the coordinator diffs successive snapshots into
    fleet-wide deltas."""
    with _lock:
        return {p: {"count": v[0], "sum_ms": v[1], "buckets": list(v[4])}
                for p, v in _phases.items()}


def phase_stats():
    """Snapshot of the attribution registry: {"steps", "spans",
    "phases": {phase: {count, total_ms, avg_ms, max_ms, last_ms}}}."""
    with _lock:
        return {
            "steps": _step_seq,
            "spans": _span_records,
            "phases": {p: {"count": v[0], "total_ms": v[1],
                           "avg_ms": v[1] / max(v[0], 1),
                           "max_ms": v[2], "last_ms": v[3]}
                       for p, v in _phases.items()},
        }


def clock_sync_event(peer, offset_us, rtt_us):
    """Record one clock-correlation sample against a remote peer as a
    ph:"M" metadata event. Args anchor this process's perf_counter trace
    timebase to its wall clock at the same instant, plus the estimated
    wall offset to the peer — tools/trace_merge.py picks the smallest-RTT
    sample per process to shift its timeline onto the server clock."""
    if not _state["running"] or _state["paused"]:
        return
    now = time.perf_counter() * 1e6
    _record("clock_sync", "__metadata", now, 0, ph="M",
            args={"peer": str(peer), "offset_us": float(offset_us),
                  "rtt_us": float(rtt_us), "perf_anchor_us": now,
                  "wall_anchor_us": time.time() * 1e6,
                  "trace": trace_id()})


# ---------------------------------------------------------------------------
# set-up rows: where a process's seconds before its first step go. Always on,
# a few hundred rows a process: nothing here runs in a steady step (jax's
# listener fires only when jax traces, lowers or builds a program).
#
# A row is (phase, name, t0, t1), seconds on time.time() (jax's own clock
# for these events), so a reader can cut the table at any instant. Phases:
#   trace   jax traced a function to a jaxpr       (name: the function's;
#   lower   jax turned the jaxpr into MLIR          from jax.monitoring's
#   build   XLA compiled it, or a cache loaded it   time spans of >= 1 ms)
#   import  incubator_mxnet_tpu/__init__.py, top to bottom (once)
#   train_step_init, make_train_step, shard_params, init_opt
#           the program's own builders (setup_span)
#   exec_lookup   a load or a compile through compile_cache (name
#           "<disk|miss>:<key>"): holds that call's trace/lower/build rows
# jax reports a jit traced inside another function's trace as a row of its
# own, so a phase's seconds are the UNION of its rows' intervals and a
# name's are its rows' self time: never plain sums. perfbench/host_spans.py
# reads the rows (setup_trace_s, setup_lower_s, setup_import_s).
# ---------------------------------------------------------------------------

_SETUP_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "build",
}
_SETUP_MAX = 4096           # the FIRST rows are the set-up: later ones drop
# Of jax's time spans only those of a millisecond or more become rows. The
# rest are counted: a GPT-2 medium or ResNet-50 start fills 4,096 rows with
# them before its step is traced (the `add`, `bitwise_xor`, ... that jax
# traces inside another trace, 15 us each at the median, 81 us on average
# on the chip's host), nearly all inside a longer row of their phase, whose
# seconds the union holds anyway.
_SETUP_MIN_S = 1e-3
_setup_rows = []            # no lock: jax calls in under anyone's, and
_setup_seq = itertools.count()      # append and next() are atomic
_setup_short_seq = itertools.count()
_setup_seen = [0, 0]        # rows offered; jax spans too short to be rows


def setup_row(phase, name, t0, t1):
    """Keep one set-up row (seconds on time.time())."""
    n = next(_setup_seq)
    _setup_seen[0] = n + 1
    if n < _SETUP_MAX:
        _setup_rows.append((phase, str(name), float(t0), float(t1)))


class setup_span:
    """`with setup_span(phase, name):` keeps the enclosed wall time as one
    set-up row."""
    __slots__ = ("_phase", "_name", "_t0")

    def __init__(self, phase, name=""):
        self._phase, self._name = phase, name

    def __enter__(self):
        self._t0 = time.time()
        return self

    def __exit__(self, *exc):
        setup_row(self._phase, self._name, self._t0, time.time())
        return False


def _reset_setup():
    global _setup_seq, _setup_short_seq
    _setup_rows.clear()
    _setup_seq, _setup_short_seq = itertools.count(), itertools.count()
    _setup_seen[:] = [0, 0]


def _on_jax_time_span(event, start_time, end_time, fun_name="", **_):
    phase = _SETUP_EVENTS.get(event)
    if phase is None:
        return
    if end_time - start_time < _SETUP_MIN_S:
        _setup_seen[1] = next(_setup_short_seq) + 1
    else:
        setup_row(phase, fun_name, start_time, end_time)


jax.monitoring.register_event_time_span_listener(_on_jax_time_span)


def _self_seconds(rows):
    """{(phase, name): seconds}: each row's duration less what the rows of
    its own phase nested inside it cover."""
    out = {}
    for phase in {r[0] for r in rows}:
        stack = []              # [name, start, end, seconds of children]

        def close(until):
            while stack and stack[-1][2] <= until:
                name, start, end, child = stack.pop()
                out[phase, name] = out.get((phase, name), 0.0) \
                    + (end - start) - child
                if stack:
                    stack[-1][3] += end - start
        for t0, neg_t1, name in sorted((r[2], -r[3], r[1]) for r in rows
                                       if r[0] == phase):
            close(t0)
            t1 = min(-neg_t1, stack[-1][2]) if stack else -neg_t1
            if t1 > t0:
                stack.append([name, t0, t1, 0.0])
        close(float("inf"))
    return out


def setup_stats(until=None, top=10):
    """The set-up table: {"rows": [(phase, name, t0, t1)], "kept", "seen"
    (rows offered: the first _SETUP_MAX are kept), "short" (jax's spans
    under a millisecond, counted and not kept), "phases": {phase: seconds,
    the union of its rows}, "top": [(phase, name, self seconds)] the `top`
    costliest names}. `until` (time.time() seconds) keeps the rows that
    ended by then: the instant of a first step makes this a start's
    set-up. What dumps() and render_prometheus() show; dumps(reset=True)
    empties the table like every other family."""
    rows = [r for r in list(_setup_rows) if until is None or r[3] <= until]
    phases = {}
    for phase in {r[0] for r in rows}:
        end, total = float("-inf"), 0.0
        for t0, t1 in sorted((r[2], r[3]) for r in rows if r[0] == phase):
            if t1 > end:
                total += t1 - max(t0, end)
                end = t1
        phases[phase] = total
    ranked = sorted(_self_seconds(rows).items(), key=lambda kv: -kv[1])
    return {"rows": rows, "kept": len(_setup_rows),
            "seen": max(len(_setup_rows), _setup_seen[0]),
            "short": _setup_seen[1], "phases": phases,
            "top": [(ph, name, sec) for (ph, name), sec in ranked[:top]]}


# ---------------------------------------------------------------------------
# compiler cost accounting: flops / bytes-accessed / peak memory per cached
# executable, recorded at the cached_jit choke points from XLA's own
# cost_analysis()/memory_analysis() — the compiler, not an analytic formula,
# is the source of truth for model FLOPs and MFU
# ---------------------------------------------------------------------------

# key -> {"flops", "bytes_accessed", "peak_bytes"} (present keys only);
# guarded by _clock next to the compile table it annotates
_costs = {}

# Published per-chip peaks keyed by jax's exact `device_kind` (Google Cloud
# documentation, "TPU v5e"). A kind that
# is not listed is an error — never a default, never a prefix match.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_sec": 819e9, "hbm_bytes": 16e9},
}


def cost_event(key, flops=None, bytes_accessed=None, peak_bytes=None):
    """Record compiler-reported cost for one executable (last write wins:
    a re-compile of the same key refreshes its cost)."""
    if _state["paused"]:
        return
    rec = {}
    for name, v in (("flops", flops), ("bytes_accessed", bytes_accessed),
                    ("peak_bytes", peak_bytes)):
        try:
            v = float(v)
        except (TypeError, ValueError):
            continue
        if v > 0 and v == v and v != float("inf"):
            rec[name] = v
    if not rec:
        return
    with _clock:
        _costs[key] = rec


def cost_from_executable(key, exe):
    """Best-effort extraction of cost_analysis()/memory_analysis() from a
    compiled executable, recorded via cost_event. Every probe is
    defensive: backends may return None, a list, or raise — cost
    accounting must never break a compile. Returns the extracted dict
    (possibly empty) so callers (bench) can reuse the numbers."""
    flops = bytes_accessed = peak = None
    try:
        ca = exe.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, dict):
            flops = ca.get("flops")
            bytes_accessed = ca.get("bytes accessed")
    except Exception:       # noqa: BLE001
        pass
    try:
        ma = exe.memory_analysis()
        total = 0.0
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v:
                total += float(v)
        if total > 0:
            peak = total
    except Exception:       # noqa: BLE001
        pass
    cost_event(key, flops=flops, bytes_accessed=bytes_accessed,
               peak_bytes=peak)
    out = {}
    with _clock:
        rec = _costs.get(key)
        if rec:
            out = dict(rec)
    return out


def cost_stats():
    """Snapshot {key: {flops, bytes_accessed, peak_bytes, intensity}}
    (intensity = flops / bytes accessed: the executable's roofline
    position; only derivable when the compiler reported both)."""
    with _clock:
        snap = {k: dict(v) for k, v in _costs.items()}
    for rec in snap.values():
        f, b = rec.get("flops"), rec.get("bytes_accessed")
        if f and b:
            rec["intensity"] = f / b
    return snap


def device_peaks():
    """The DEVICE_PEAKS row of device 0; an unlisted kind raises."""
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise MXNetError(
            f"no published peaks for device kind {kind!r}; add its row to "
            f"profiler.DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]


def mfu_stats():
    """MFU derived from compiler cost accounting instead of analytic FLOP
    formulas: model FLOPs/step come from the most-called trainstep
    executable's cost_analysis() and seconds/step from the attributed
    'compute' phase. Returns None until both ingredients exist; "mfu" is
    null off-TPU (no trustworthy peak), the flops rate is still real."""
    with _clock:
        calls = {k: v[0] + v[1] for k, v in _compile.items()}
        costs = {k: dict(v) for k, v in _costs.items()}
    best = None
    for key, rec in costs.items():
        if not key.startswith("trainstep:") or not rec.get("flops"):
            continue
        c = calls.get(key, 0)
        if best is None or c > best[1]:
            best = (key, c, rec)
    if best is None:
        return None
    key, _, rec = best
    with _lock:
        comp = _phases.get("compute")
        compute_ms = comp[1] / max(comp[0], 1) if comp else None
        bub = _phases.get("pp_bubble")
        bubble_ms = bub[1] / max(bub[0], 1) if bub else None
    with _lock:
        # last sample wins: the activation-offload counters (booked by the
        # composed step / HostOffloader) ride along so an offload run's
        # D2H traffic shows up next to its MFU
        offl = {}
        for name, _ts, val in _counters:
            if name in ("d2h_bytes", "offload_wait_ms_per_step"):
                offl[name] = val
    out = {"key": key, "flops_per_step": rec["flops"],
           "bytes_per_step": rec.get("bytes_accessed"),
           "compute_ms_per_step": compute_ms,
           "pp_bubble_ms_per_step": bubble_ms,
           "pp_bubble_fraction": None,
           "d2h_bytes": offl.get("d2h_bytes"),
           "offload_wait_ms_per_step": offl.get("offload_wait_ms_per_step"),
           # no trustworthy peak on the CPU backend: MFU is then null
           # rather than a made-up number; an unlisted accelerator raises
           "peak_flops": None if jax.default_backend() == "cpu"
           else device_peaks()["bf16_flops"],
           "flops_per_sec": None, "mfu": None}
    if compute_ms:
        out["flops_per_sec"] = rec["flops"] / (compute_ms / 1e3)
        if out["peak_flops"]:
            out["mfu"] = out["flops_per_sec"] / out["peak_flops"]
        if bubble_ms is not None:
            out["pp_bubble_fraction"] = bubble_ms / (bubble_ms + compute_ms)
    return out


# ---------------------------------------------------------------------------
# memory profiler (reference storage_profiler.h GpuDeviceStorageProfiler,
# enabled by the same `profile_memory` config flag the reference uses)
# ---------------------------------------------------------------------------

# The weakref finalizer (_note_free) takes NO locks: GC can run it on a
# thread that is mid-critical-section under _mlock or _lock (allocations
# inside those sections can trigger a collection), so any acquisition
# there would self-deadlock. It only appends to _pending_frees (atomic
# under the GIL); the books are settled at the next drain point
# (_note_alloc / memory_stats / render_prometheus).
_mlock = _mxsan.lock("profiler.py", "_mlock")
_mem = {
    "enabled": False,
    "live": defaultdict(int),     # device label -> live bytes
    "peak": defaultdict(int),     # device label -> peak bytes
    "buffers": {},                # id(buf) -> (nbytes, device label)
    "allocs": 0,                  # cumulative allocation events
    "frees": 0,
}
_pending_frees = []               # buffer keys enqueued by finalizers

_scope_tls = threading.local()


def _current_scope():
    stack = getattr(_scope_tls, "stack", None)
    return stack[-1] if stack else None


def memory_enabled():
    return _mem["enabled"]


def _device_of(buf):
    try:
        devs = buf.devices()
        if len(devs) == 1:
            return str(next(iter(devs)))
        return f"mesh[{len(devs)}]"
    except Exception:       # noqa: BLE001 — committed-less / host arrays
        return "uncommitted"


def _note_free(key):
    # weakref.finalize callback — must stay lock-free (see _mlock comment)
    _pending_frees.append(key)


def _drain_frees_locked():
    """Settle queued finalizer frees into the books. Caller holds _mlock.
    Returns {device: live_bytes_after} for devices that changed."""
    changed = {}
    while _pending_frees:
        try:
            key = _pending_frees.pop()
        except IndexError:      # lost a race to a concurrent drain
            break
        rec = _mem["buffers"].pop(key, None)
        if rec is None:
            continue
        nbytes, dev = rec
        _mem["live"][dev] -= nbytes
        _mem["frees"] += 1
        changed[dev] = _mem["live"][dev]
    return changed


def _drain_frees():
    with _mlock:
        changed = _drain_frees_locked()
    if changed and is_running():
        for dev, live in changed.items():
            _counter_sample(f"memory:live_bytes:{dev}", live)


def _note_alloc(buf, tag=None):
    """Account one device buffer (installed as ndarray.MEMORY_HOOK while
    profile_memory is active; also called explicitly from donation paths
    that swap raw jax buffers without constructing an NDArray). Duplicate
    registrations of the same live buffer are no-ops, so wrapper churn
    (views, out= rebinds) never double-counts."""
    if not _mem["enabled"]:
        return
    try:
        nbytes = int(buf.nbytes)
    except Exception:       # noqa: BLE001 — tracers, abstract values
        return
    key = id(buf)
    # settle queued frees first: a dead buffer's id() can be recycled by
    # this very allocation, and its stale entry would mask the new one
    _drain_frees()
    with _mlock:
        if key in _mem["buffers"]:
            return
    try:
        weakref.finalize(buf, _note_free, key)
    except TypeError:
        return              # not weakref-able: cannot track its lifetime
    dev = _device_of(buf)
    with _mlock:
        if key in _mem["buffers"]:      # lost a thread race — already in
            return
        _mem["buffers"][key] = (nbytes, dev)
        _mem["live"][dev] += nbytes
        if _mem["live"][dev] > _mem["peak"][dev]:
            _mem["peak"][dev] = _mem["live"][dev]
        _mem["allocs"] += 1
        live = _mem["live"][dev]
    if is_running():
        now = time.perf_counter() * 1e6
        scope = tag or _current_scope() or "global"
        with _lock:
            _counter_sample_locked(f"memory:live_bytes:{dev}", live)
            _events.append({"name": f"alloc:{scope}", "cat": "memory",
                            "ts": now, "dur": 0,
                            "tid": threading.get_ident(), "ph": "i",
                            "s": "t",
                            "args": {"bytes": nbytes, "device": dev}})


def memory_event(arr, tag=None):
    """Explicitly account a buffer created outside NDArray construction
    (fused-step donation outputs, sparse containers). `arr` may be an
    NDArray or a raw jax array."""
    data = getattr(arr, "_data", arr)
    _note_alloc(data, tag=tag)


def memory_stats():
    """Pure-python accounting snapshot: per-device live/peak bytes plus
    whatever the backend itself reports (jax.live_arrays byte total,
    device memory_stats) when available."""
    _drain_frees()
    with _mlock:
        snap = {
            "live_bytes": dict(_mem["live"]),
            "peak_bytes": dict(_mem["peak"]),
            "tracked_buffers": len(_mem["buffers"]),
            "alloc_events": _mem["allocs"],
            "free_events": _mem["frees"],
        }
    try:
        snap["jax_live_bytes"] = int(sum(
            getattr(a, "nbytes", 0) for a in jax.live_arrays()))
        dev_stats = {}
        for d in jax.local_devices():
            try:
                s = d.memory_stats()
            except Exception:       # noqa: BLE001
                s = None
            if s:
                dev_stats[str(d)] = {
                    k: int(v) for k, v in s.items()
                    if k in ("bytes_in_use", "peak_bytes_in_use",
                             "bytes_limit")}
        if dev_stats:
            snap["device_memory_stats"] = dev_stats
    except Exception:       # noqa: BLE001 — no backend, headless dumps
        pass
    return snap


def _reset_memory_locked():
    """reset=True semantics: peaks collapse to the current live level and
    the event counts restart; live accounting keeps tracking the buffers
    that are still alive (dropping them would corrupt the books)."""
    with _mlock:
        _drain_frees_locked()
        for dev, live in _mem["live"].items():
            _mem["peak"][dev] = live
        _mem["allocs"] = 0
        _mem["frees"] = 0


def _exec_cache_stats(always=False):
    """Aggregate counters of the two-tier executable cache
    (compile_cache.stats()), or None when it has seen no traffic (unless
    `always`) — keeps dumps() noise-free for sessions that never jit."""
    try:
        from . import compile_cache as _cc
        snap = _cc.stats()
    except Exception:       # noqa: BLE001 — torn-down interpreter, no jax
        return None
    if not always and not any(snap.values()):
        return None
    return snap


def _tune_stats(always=False):
    """Aggregate counters of the kernel autotuner (tune.stats()), or None
    when no tuned_call site ran (unless `always`)."""
    try:
        from . import tune as _tn
        snap = _tn.stats()
    except Exception:       # noqa: BLE001 — torn-down interpreter, no jax
        return None
    if not always and not any(snap.values()):
        return None
    return snap


def _shardlint_stats(always=False):
    """Graph-capture counters (shardlint.stats(): enabled flag, buffered
    captures by kind, drops), or None when capture is off and nothing was
    ever recorded (unless `always`)."""
    try:
        from . import shardlint as _sl
        snap = _sl.stats()
    except Exception:       # noqa: BLE001 — torn-down interpreter
        return None
    if not always and not any(snap.values()):
        return None
    return snap


def _fault_stats(always=False):
    """Fault-tolerance counters (fault.stats(): checkpoints, heartbeats,
    dead/straggler sightings, rejoins), or None when the process did no
    fault-tolerance work (unless `always`)."""
    try:
        from . import fault as _ft
        snap = _ft.stats()
    except Exception:       # noqa: BLE001 — torn-down interpreter
        return None
    if not always and not any(snap.values()):
        return None
    return snap


def _fleetobs_stats(always=False):
    """Fleet-observability counters (fleetobs.stats(): snapshots built/
    folded, SLO evaluations, alert transitions, remote-profile traffic),
    or None when the plane saw no traffic (unless `always`)."""
    try:
        from . import fleetobs as _fo
        snap = _fo.stats()
    except Exception:       # noqa: BLE001 — torn-down interpreter
        return None
    if not always and not any(snap.values()):
        return None
    return snap


def _mxsan_stats(always=False):
    """Concurrency-sanitizer counters (mxsan.stats(): acquisitions
    witnessed, observed lock-order edges, blocking-under-lock sightings,
    re-entries, cycles), or None while the MXNET_MXSAN gate is off and
    nothing was recorded (unless `always`)."""
    try:
        from . import mxsan as _mx
        snap = _mx.stats()
    except Exception:       # noqa: BLE001 — torn-down interpreter
        return None
    if not always and not any(snap.values()):
        return None
    return snap


# ---------------------------------------------------------------------------
# dump / dumps
# ---------------------------------------------------------------------------

def _fold_aggregates_locked(events, counters):
    """Fold trimmed buffers into the persistent aggregates (caller holds
    _lock) so dumps() keeps whole-run stats after rolling dumps discard
    the raw events."""
    for ev in events:
        a = _agg_events.get(ev["name"])
        if a is None:
            _agg_events[ev["name"]] = [1, ev["dur"], ev["dur"], ev["dur"]]
        else:
            a[0] += 1
            a[1] += ev["dur"]
            a[2] = min(a[2], ev["dur"])
            a[3] = max(a[3], ev["dur"])
    for name, _ts, _value in counters:
        _agg_counts[name] = _agg_counts.get(name, 0) + 1


def _segment_path(seq):
    root, ext = os.path.splitext(_state["filename"])
    return f"{root}.{seq:04d}{ext or '.json'}"


def dump(finished=True, profile_process="worker"):
    """Write chrome://tracing JSON (reference MXDumpProfile;
    profiler.h:79 'chrome tracing json'). `finished=False` (the continuous
    dump path) writes a bounded *segment* file (`<name>.NNNN.json`) holding
    only the events since the previous rolling dump and clears the buffers
    — a long run produces a sequence of small traces instead of one
    ever-growing file re-serialized every period. Trimmed events are folded
    into the aggregate registry so dumps() still covers the whole run."""
    global _dump_seq
    with _lock:
        events = list(_events)
        counters = list(_counters)
        if finished:
            _events.clear()
            _counters.clear()
            _agg_events.clear()
            _agg_counts.clear()
        else:
            if not events and not counters:
                return None     # quiet period: no empty segment spam
            _events.clear()
            _counters.clear()
            _fold_aggregates_locked(events, counters)
            seq, _dump_seq = _dump_seq, _dump_seq + 1
    path = _state["filename"] if finished else _segment_path(seq)
    trace = []
    for ev in events:
        e = {"name": ev["name"], "cat": ev["cat"], "ph": ev["ph"],
             "ts": ev["ts"], "pid": 0, "tid": ev["tid"]}
        if ev["ph"] == "X":
            e["dur"] = ev["dur"]
        if "s" in ev:
            e["s"] = ev["s"]
        if "args" in ev:
            e["args"] = ev["args"]
        trace.append(e)
    for name, ts, value in counters:
        trace.append({"name": name, "ph": "C", "ts": ts, "pid": 0,
                      "args": {"value": _finite(value, 0)}})
    if attribution_enabled():
        # self clock anchor: maps this process's perf_counter trace
        # timebase onto its own wall clock, so tools/trace_merge.py can
        # place it on a shared timeline even when no peer clock_sync
        # sample exists (the server side never dials anyone)
        trace.append({"name": "clock_sync", "cat": "__metadata", "ph": "M",
                      "ts": 0, "pid": 0, "tid": 0,
                      "args": {"peer": "self", "offset_us": 0.0,
                               "rtt_us": 0.0,
                               "perf_anchor_us": time.perf_counter() * 1e6,
                               "wall_anchor_us": time.time() * 1e6,
                               "trace": trace_id()}})
    with open(path, "w") as f:
        json.dump({"traceEvents": trace, "displayTimeUnit": "ms"}, f)
    return path


def _finite(v, default=None):
    """Strict-JSON guard: bare Infinity/NaN from json.dumps is rejected by
    conforming parsers; non-finite aggregates serialize as `default`."""
    if isinstance(v, float) and (v != v or v in (float("inf"),
                                                 float("-inf"))):
        return default
    return v


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Aggregate-stats string (reference MXAggregateProfileStatsPrint /
    aggregate_stats.cc). Sections:

    - per-op event table (count/total/min/max/avg us)
    - counter series (last value + sample count per name)
    - compile cache table (hits/misses/compile ms per tracked jit cache)
    - memory table (per-device live/peak bytes) when profile_memory ran

    format="json" returns the same data as a strict-JSON object (non-finite
    aggregates are null, so json.loads in strict consumers round-trips).
    """
    with _lock:
        events = list(_events)
        counters = list(_counters)
        folded = {k: list(v) for k, v in _agg_events.items()}
        folded_counts = dict(_agg_counts)
        last = dict(_counter_last)
        if reset:
            _events.clear()
            _counters.clear()
            _agg_events.clear()
            _agg_counts.clear()
    agg = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
    for name, (cnt, tot, mn, mx) in folded.items():
        agg[name] = [cnt, tot, mn, mx]
    for ev in events:
        a = agg[ev["name"]]
        a[0] += 1
        a[1] += ev["dur"]
        a[2] = min(a[2], ev["dur"])
        a[3] = max(a[3], ev["dur"])
    # counter series trimmed by rolling dumps contribute their sample
    # count; the latest value comes from the gauge registry
    cagg = {name: (cnt, last.get(name, 0))
            for name, cnt in folded_counts.items()}
    for name, ts, value in counters:
        cnt = cagg[name][0] + 1 if name in cagg else 1
        cagg[name] = (cnt, value)
    comp = compile_stats()
    mem = memory_stats() if (_mem["enabled"] or _mem["allocs"]
                             or _mem["peak"]) else None
    attr = phase_stats()
    costs = cost_stats()
    mfu = mfu_stats()
    setup = setup_stats()
    exec_cache = _exec_cache_stats()
    tune_snap = _tune_stats()
    fault_snap = _fault_stats()
    sl_snap = _shardlint_stats()
    fleet_snap = _fleetobs_stats()
    mxsan_snap = _mxsan_stats()
    if reset:
        # reset=True means reset: every stat family this dump reports
        # restarts, not just the event/counter/compile subset (the old
        # behavior left exec-cache/tune/fault/shardlint counters — and
        # their disk counters — accumulating across "reset" windows)
        with _clock:
            _compile.clear()
            _compile_warned.clear()
            _costs.clear()
        with _lock:
            _reset_phases_locked()
        _reset_setup()
        _reset_memory_locked()
        try:
            from . import compile_cache as _cc
            _cc.clear(memory=False, disk=False, stats=True)
        except Exception:       # noqa: BLE001 — torn-down interpreter
            pass
        try:
            from . import tune as _tn
            _tn.clear(memory=False, stats=True)
        except Exception:       # noqa: BLE001
            pass
        try:
            from . import fault as _ft
            _ft._reset_stats()
        except Exception:       # noqa: BLE001
            pass
        try:
            from . import shardlint as _sl
            _sl.clear(stats=True)
        except Exception:       # noqa: BLE001
            pass
        try:
            from . import fleetobs as _fo
            _fo.clear(stats=True)
        except Exception:       # noqa: BLE001
            pass
        try:
            from . import mxsan as _mx
            _mx.clear(stats=True)
        except Exception:       # noqa: BLE001
            pass
    if format == "json":
        out = {
            "stats": {k: {"count": v[0], "total_us": _finite(v[1], 0.0),
                          "min_us": _finite(v[2]), "max_us": _finite(v[3])}
                      for k, v in agg.items()},
            "counters": {k: {"samples": c, "value": _finite(v)}
                         for k, (c, v) in cagg.items()},
            "compile": comp,
        }
        if attr["phases"] or attr["steps"]:
            out["step_attribution"] = {
                "steps": attr["steps"], "spans": attr["spans"],
                "phases": {p: {k: _finite(v) for k, v in rec.items()}
                           for p, rec in attr["phases"].items()}}
        if costs:
            out["cost"] = costs
        if mfu is not None:
            out["mfu"] = {k: _finite(v) for k, v in mfu.items()}
        if setup["rows"]:
            out["setup"] = {k: setup[k] for k in ("phases", "top", "kept",
                                                  "seen", "short")}
        if exec_cache is not None:
            out["exec_cache"] = exec_cache
        if tune_snap is not None:
            out["tune"] = tune_snap
        if fault_snap is not None:
            out["fault"] = fault_snap
        if sl_snap is not None:
            out["shardlint"] = sl_snap
        if fleet_snap is not None:
            out["fleetobs"] = fleet_snap
        if mxsan_snap is not None:
            out["mxsan"] = mxsan_snap
        if mem is not None:
            out["memory"] = {"live_bytes": mem["live_bytes"],
                             "peak_bytes": mem["peak_bytes"],
                             "alloc_events": mem["alloc_events"]}
        return json.dumps(out)
    lines = [f"{'Name':<40}{'Count':>8}{'Total(us)':>14}"
             f"{'Min(us)':>12}{'Max(us)':>12}{'Avg(us)':>12}",
             "-" * 98]
    key = {"total": lambda kv: kv[1][1], "count": lambda kv: kv[1][0],
           "min": lambda kv: kv[1][2], "max": lambda kv: kv[1][3],
           "avg": lambda kv: kv[1][1] / max(kv[1][0], 1)}[sort_by]
    for name, (cnt, tot, mn, mx) in sorted(agg.items(), key=key,
                                           reverse=not ascending):
        mn = 0.0 if mn == float("inf") else mn
        lines.append(f"{name:<40}{cnt:>8}{tot:>14.1f}{mn:>12.1f}"
                     f"{mx:>12.1f}{tot / max(cnt, 1):>12.1f}")
    if cagg:
        lines += ["", f"{'Counter':<48}{'Samples':>10}{'Value':>16}",
                  "-" * 74]
        for name, (cnt, val) in sorted(cagg.items()):
            sval = f"{val:.3f}" if isinstance(val, float) else f"{val}"
            lines.append(f"{name:<48}{cnt:>10}{sval:>16}")
    if attr["phases"]:
        lines += ["", f"{'Step breakdown (phase)':<28}{'Count':>8}"
                      f"{'ms/step':>12}{'Total(ms)':>12}{'Max(ms)':>12}"
                      f"{'Last(ms)':>12}",
                  "-" * 84]
        for p, rec in sorted(attr["phases"].items(),
                             key=lambda kv: -kv[1]["total_ms"]):
            lines.append(f"{p:<28}{rec['count']:>8}{rec['avg_ms']:>12.3f}"
                         f"{rec['total_ms']:>12.1f}{rec['max_ms']:>12.3f}"
                         f"{rec['last_ms']:>12.3f}")
        lines.append(f"{'(steps closed)':<28}{attr['steps']:>8}")
    if comp:
        lines += ["", f"{'Compile cache':<48}{'Hits':>8}{'Disk':>8}"
                      f"{'Misses':>8}{'Compile(ms)':>14}",
                  "-" * 86]
        for name, rec in sorted(comp.items()):
            lines.append(f"{name:<48}{rec['hits']:>8}"
                         f"{rec.get('disk_hits', 0):>8}{rec['misses']:>8}"
                         f"{rec['compile_ms']:>14.1f}")
    if costs:
        lines += ["", f"{'Compiler cost (per executable)':<48}"
                      f"{'GFLOP':>10}{'MB':>10}{'F/B':>8}",
                  "-" * 76]
        for name, rec in sorted(costs.items()):
            gf = rec.get("flops")
            mb = rec.get("bytes_accessed")
            it = rec.get("intensity")
            lines.append(
                f"{name:<48}"
                + (f"{gf / 1e9:>10.3f}" if gf else f"{'-':>10}")
                + (f"{mb / 1e6:>10.2f}" if mb else f"{'-':>10}")
                + (f"{it:>8.1f}" if it else f"{'-':>8}"))
    if mfu is not None:
        lines += ["", f"{'MFU (compiler cost / compute phase)':<48}"]
        lines.append(f"  key={mfu['key']}  "
                     f"flops/step={mfu['flops_per_step']:.3e}"
                     + (f"  compute={mfu['compute_ms_per_step']:.3f}ms"
                        if mfu["compute_ms_per_step"] else "")
                     + (f"  MFU={mfu['mfu'] * 100:.1f}%"
                        if mfu["mfu"] is not None else "  MFU=n/a"))
    if setup["rows"]:
        lines += ["", f"{'Set-up (phase; costliest names)':<64}"
                      f"{'Seconds':>12}",
                  "-" * 76]
        for ph, sec in sorted(setup["phases"].items(), key=lambda kv: -kv[1]):
            lines.append(f"{ph:<64}{sec:>12.3f}")
        for ph, name, sec in setup["top"]:
            lines.append(f"{'  ' + ph + ' ' + name[:56]:<64}{sec:>12.3f}")
        lines.append(f"{'(rows kept / seen; spans under 1 ms, not kept)':<56}"
                     f"{setup['kept']:>6} /{setup['seen']:>6};"
                     f"{setup['short']:>6}")
    if exec_cache is not None:
        lines += ["", f"{'Executable cache (two-tier)':<34}{'Value':>12}",
                  "-" * 46]
        for k in ("hits", "misses", "disk_hits", "evictions", "bytes",
                  "disk_errors", "fallbacks", "mem_entries"):
            lines.append(f"{'exec_cache_' + k:<34}{exec_cache[k]:>12}")
    if tune_snap is not None:
        lines += ["", f"{'Kernel autotuner':<34}{'Value':>12}",
                  "-" * 46]
        for k in ("searches", "hits", "disk_hits", "disk_errors",
                  "fallbacks", "withheld", "cand_errors",
                  "cand_mismatches", "cand_lost", "winners"):
            lines.append(f"{'tune_' + k:<34}{tune_snap[k]:>12}")
    if fault_snap is not None:
        lines += ["", f"{'Fault tolerance':<34}{'Value':>12}",
                  "-" * 46]
        for k in sorted(fault_snap):
            v = fault_snap[k]
            sval = f"{v:.1f}" if isinstance(v, float) else f"{v}"
            lines.append(f"{'fault_' + k:<34}{sval:>12}")
    if sl_snap is not None:
        lines += ["", f"{'Graph capture (shardlint)':<34}{'Value':>12}",
                  "-" * 46]
        for k in ("enabled", "captures", "jit", "tuned", "partition",
                  "dropped"):
            lines.append(f"{'shardlint_' + k:<34}{sl_snap[k]:>12}")
    if fleet_snap is not None:
        lines += ["", f"{'Fleet observability (fleetobs)':<34}{'Value':>12}",
                  "-" * 46]
        for k in sorted(fleet_snap):
            lines.append(f"{'fleet_' + k:<34}{fleet_snap[k]:>12}")
    if mxsan_snap is not None:
        lines += ["", f"{'Concurrency sanitizer (mxsan)':<34}{'Value':>12}",
                  "-" * 46]
        for k in ("enabled", "records", "acquires", "edges", "blocking",
                  "reentries", "cycles", "threads", "dropped"):
            lines.append(f"{'mxsan_' + k:<34}{int(mxsan_snap[k]):>12}")
    if mem is not None and (mem["live_bytes"] or mem["peak_bytes"]):
        lines += ["", f"{'Memory (device)':<48}{'Live(bytes)':>14}"
                      f"{'Peak(bytes)':>14}",
                  "-" * 76]
        devs = sorted(set(mem["live_bytes"]) | set(mem["peak_bytes"]))
        for dev in devs:
            lines.append(f"{dev:<48}{mem['live_bytes'].get(dev, 0):>14}"
                         f"{mem['peak_bytes'].get(dev, 0):>14}")
        lines.append(f"{'(alloc events)':<48}"
                     f"{mem['alloc_events']:>14}{mem['free_events']:>14}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Prometheus text exposition (the /metrics scrape surface)
# ---------------------------------------------------------------------------

_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_label(value):
    return (str(value).replace("\\", r"\\").replace('"', r"\"")
            .replace("\n", r"\n"))


def render_prometheus():
    """Serialize the live telemetry registries in Prometheus text
    exposition format (served by serve/server.py at GET /metrics):

    - every profiler Counter's last value as
      mxnet_profiler_counter{name="..."}
    - per-cache compile hits/misses/compile-time totals
    - per-device live/peak memory bytes (when profile_memory ran)
    - profiler liveness + buffered event/sample gauges
    """
    lines = []

    def family(name, mtype, help_text):
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {mtype}")

    family("mxnet_profiler_running", "gauge",
           "1 while the profiler is collecting")
    lines.append(f"mxnet_profiler_running {1 if is_running() else 0}")

    with _lock:
        last = dict(_counter_last)
        n_events = len(_events)
        n_samples = len(_counters)
    family("mxnet_profiler_buffered_events", "gauge",
           "trace events buffered since the last dump")
    lines.append(f"mxnet_profiler_buffered_events {n_events}")
    family("mxnet_profiler_buffered_counter_samples", "gauge",
           "counter samples buffered since the last dump")
    lines.append(f"mxnet_profiler_buffered_counter_samples {n_samples}")

    if last:
        family("mxnet_profiler_counter", "gauge",
               "last value of each profiler counter series")
        for name in sorted(last):
            val = _finite(last[name])
            if not isinstance(val, (int, float)) or isinstance(val, bool):
                continue
            lines.append(
                f'mxnet_profiler_counter{{name="{_prom_label(name)}"}} '
                f'{val}')

    comp = compile_stats()
    if comp:
        family("mxnet_compile_cache_hits_total", "counter",
               "compiled-executable reuses per jit cache key")
        for name in sorted(comp):
            lines.append(
                f'mxnet_compile_cache_hits_total'
                f'{{key="{_prom_label(name)}"}} {comp[name]["hits"]}')
        family("mxnet_compile_cache_misses_total", "counter",
               "XLA (re)compilations per jit cache key")
        for name in sorted(comp):
            lines.append(
                f'mxnet_compile_cache_misses_total'
                f'{{key="{_prom_label(name)}"}} {comp[name]["misses"]}')
        family("mxnet_compile_cache_disk_hits_total", "counter",
               "persistent-cache deserialize hits per jit cache key "
               "(hits that a cold process would otherwise pay as "
               "recompiles)")
        for name in sorted(comp):
            lines.append(
                f'mxnet_compile_cache_disk_hits_total'
                f'{{key="{_prom_label(name)}"}} '
                f'{comp[name].get("disk_hits", 0)}')
        family("mxnet_compile_time_ms_total", "counter",
               "wall-clock ms spent tracing+compiling per jit cache key")
        for name in sorted(comp):
            lines.append(
                f'mxnet_compile_time_ms_total'
                f'{{key="{_prom_label(name)}"}} '
                f'{comp[name]["compile_ms"]:.3f}')

    with _lock:
        phase_snap = {p: (v[0], v[1], list(v[4])) for p, v in _phases.items()}
    if phase_snap:
        family("mxnet_step_phase_ms", "histogram",
               "attributed per-phase step time in ms "
               "(MXNET_STEP_ATTRIBUTION)")
        for p in sorted(phase_snap):
            cnt, total, buckets = phase_snap[p]
            lbl = _prom_label(p)
            cum = 0
            for i, b in enumerate(_PHASE_BOUNDS):
                cum += buckets[i]
                lines.append(
                    f'mxnet_step_phase_ms_bucket{{phase="{lbl}",'
                    f'le="{b:.6g}"}} {cum}')
            cum += buckets[-1]
            lines.append(
                f'mxnet_step_phase_ms_bucket{{phase="{lbl}",le="+Inf"}} '
                f'{cum}')
            lines.append(f'mxnet_step_phase_ms_sum{{phase="{lbl}"}} '
                         f'{total:.3f}')
            lines.append(f'mxnet_step_phase_ms_count{{phase="{lbl}"}} '
                         f'{cnt}')

    setup = setup_stats()
    if setup["rows"]:
        family("mxnet_setup_phase_seconds", "gauge",
               "seconds this process spent in each set-up phase (union of "
               "the phase's rows: trace, lower, build, import, ...)")
        for ph in sorted(setup["phases"]):
            lines.append(f'mxnet_setup_phase_seconds'
                         f'{{phase="{_prom_label(ph)}"}} '
                         f'{setup["phases"][ph]:.6f}')
        family("mxnet_setup_name_seconds", "gauge",
               "self seconds of the ten costliest names of the set-up table")
        for ph, name, sec in setup["top"]:
            lines.append(f'mxnet_setup_name_seconds'
                         f'{{phase="{_prom_label(ph)}",'
                         f'name="{_prom_label(name)}"}} {sec:.6f}')

    costs = cost_stats()
    if costs:
        _COST_FAMILIES = (
            ("flops", "mxnet_executable_flops",
             "compiler cost_analysis FLOPs per call of this executable"),
            ("bytes_accessed", "mxnet_executable_bytes_accessed",
             "compiler cost_analysis bytes accessed per call"),
            ("peak_bytes", "mxnet_executable_peak_bytes",
             "compiler memory_analysis arg+output+temp bytes"),
            ("intensity", "mxnet_executable_intensity",
             "roofline arithmetic intensity (flops per byte accessed)"),
        )
        for stat, fam, help_text in _COST_FAMILIES:
            rows = [(k, v[stat]) for k, v in sorted(costs.items())
                    if v.get(stat)]
            if not rows:
                continue
            family(fam, "gauge", help_text)
            for key, v in rows:
                lines.append(f'{fam}{{key="{_prom_label(key)}"}} {v:.6g}')
    mfu = mfu_stats()
    if mfu is not None:
        family("mxnet_model_flops_per_step", "gauge",
               "model FLOPs per train step from compiler cost accounting")
        lines.append(
            f"mxnet_model_flops_per_step {mfu['flops_per_step']:.6g}")
        if mfu["mfu"] is not None:
            family("mxnet_mfu_ratio", "gauge",
                   "model FLOP utilization from cost_analysis over the "
                   "attributed compute phase")
            lines.append(f"mxnet_mfu_ratio {mfu['mfu']:.6g}")

    ec = _exec_cache_stats(always=True)
    if ec is not None:
        _EC_FAMILIES = (
            ("hits", "counter", "exec-cache memory-tier hits"),
            ("misses", "counter", "exec-cache XLA trace+compiles"),
            ("disk_hits", "counter",
             "exec-cache persistent-tier deserialize hits"),
            ("evictions", "counter",
             "exec-cache LRU + disk-budget evictions"),
            ("bytes", "gauge", "exec-cache disk occupancy in bytes"),
            ("entries", "gauge", "exec-cache in-memory executables"),
        )
        for stat, mtype, help_text in _EC_FAMILIES:
            value = ec["mem_entries"] if stat == "entries" else ec[stat]
            suffix = "_total" if mtype == "counter" else ""
            family(f"mxnet_exec_cache_{stat}{suffix}", mtype, help_text)
            lines.append(f"mxnet_exec_cache_{stat}{suffix} {value}")

    tn = _tune_stats(always=True)
    if tn is not None:
        _TUNE_FAMILIES = (
            ("searches", "counter",
             "autotuner candidate sweeps timed (or trivially decided)"),
            ("hits", "counter", "autotuner memory-table winner lookups"),
            ("disk_hits", "counter",
             "autotuner winners re-loaded from the persistent store"),
            ("disk_errors", "counter",
             "corrupt/stale/unwritable autotuner winner files"),
            ("fallbacks", "counter",
             "tuned_call dispatches that fell back to the XLA path"),
            ("withheld", "counter",
             "tuned_call dispatches inside a tune.xla_only() scope"),
            ("cand_errors", "counter",
             "autotuner candidates (or builders) that raised"),
            ("cand_mismatches", "counter",
             "autotuner candidates that diverged from the XLA reference"),
            ("cand_lost", "counter",
             "autotuner candidates that ran, matched, and were slower"),
            ("winners", "gauge", "tuned winners resident in memory"),
        )
        for stat, mtype, help_text in _TUNE_FAMILIES:
            suffix = "_total" if mtype == "counter" else ""
            family(f"mxnet_tune_{stat}{suffix}", mtype, help_text)
            lines.append(f"mxnet_tune_{stat}{suffix} {tn[stat]}")

    sl = _shardlint_stats(always=True)
    if sl is not None:
        _SL_FAMILIES = (
            ("enabled", "gauge",
             "1 while MXNET_SHARDLINT graph capture is on"),
            ("captures", "gauge",
             "shardlint captures currently buffered"),
            ("jit", "counter",
             "jaxpr captures recorded at the jit choke points"),
            ("tuned", "counter",
             "tuned_call dispatch records captured"),
            ("partition", "counter",
             "partition-rule coverage reports captured"),
            ("dropped", "counter",
             "captures evicted by the bounded buffer"),
        )
        for stat, mtype, help_text in _SL_FAMILIES:
            suffix = "_total" if mtype == "counter" else ""
            family(f"mxnet_shardlint_{stat}{suffix}", mtype, help_text)
            lines.append(f"mxnet_shardlint_{stat}{suffix} {sl[stat]}")

    ft = _fault_stats(always=True)
    if ft is not None:
        # mxnet_worker_*: the fleet-health scrape surface — liveness,
        # stragglers, elastic rejoins, and write-behind checkpoint health
        _WORKER_FAMILIES = (
            ("heartbeats_sent", "heartbeats_total", "counter",
             "liveness beats sent to the dist_async server registry"),
            ("dead_nodes_seen", "dead_nodes_total", "counter",
             "cumulative dead ranks reported by get_dead_nodes"),
            ("stragglers_seen", "stragglers_total", "counter",
             "cumulative straggler ranks reported (step lag >= "
             "MXNET_STRAGGLER_LAG)"),
            ("rejoins", "rejoins_total", "counter",
             "elastic re-registrations reclaiming a dead rank"),
            ("membership_changes", "membership_changes_total", "counter",
             "server membership epoch changes observed via heartbeats"),
            ("ckpt_saves", "checkpoint_saves_total", "counter",
             "checkpoint generations committed to disk"),
            ("ckpt_dropped", "checkpoint_dropped_total", "counter",
             "pending snapshots dropped by the bounded write-behind queue"),
            ("ckpt_errors", "checkpoint_errors_total", "counter",
             "background checkpoint write failures"),
            ("ckpt_fallbacks", "checkpoint_fallbacks_total", "counter",
             "corrupt checkpoint generations skipped at restore"),
            ("ckpt_write_ms", "checkpoint_write_ms_total", "counter",
             "wall-clock ms spent writing checkpoints off the step path"),
            ("ckpt_last_step", "checkpoint_last_step", "gauge",
             "newest step durably checkpointed"),
            ("faults_injected", "faults_injected_total", "counter",
             "MXNET_FAULT_INJECT actions fired (tests only)"),
            ("slo_alerts", "slo_alerts_total", "counter",
             "fleet SLO alerts raised by the fleetobs burn-rate engine"),
        )
        for stat, prom, mtype, help_text in _WORKER_FAMILIES:
            family(f"mxnet_worker_{prom}", mtype, help_text)
            v = ft[stat]
            v = f"{v:.3f}" if isinstance(v, float) else f"{v}"
            lines.append(f"mxnet_worker_{prom} {v}")

    # mxnet_mxsan_*: the concurrency-sanitizer surface. mxsan renders
    # its own block and returns "" until the first record, so a gate-off
    # scrape stays byte-identical to a build without the sanitizer.
    try:
        from . import mxsan as _mx
        san = _mx.render_prometheus().rstrip("\n")
    except Exception:       # noqa: BLE001 — torn-down interpreter
        san = ""
    if san:
        lines.append(san)

    _drain_frees()
    with _mlock:
        live = dict(_mem["live"])
        peak = dict(_mem["peak"])
    if live or peak:
        family("mxnet_memory_live_bytes", "gauge",
               "python-accounted live device bytes (profile_memory)")
        for dev in sorted(live):
            lines.append(
                f'mxnet_memory_live_bytes{{device="{_prom_label(dev)}"}} '
                f'{live[dev]}')
        family("mxnet_memory_peak_bytes", "gauge",
               "python-accounted peak device bytes (profile_memory)")
        for dev in sorted(peak):
            lines.append(
                f'mxnet_memory_peak_bytes{{device="{_prom_label(dev)}"}} '
                f'{peak[dev]}')

    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# user objects: Domain / Scope / Task / Event / Marker / Counter
# ---------------------------------------------------------------------------

class Domain:
    """Named grouping for Tasks/Counters/Markers (reference profiler.py
    Domain / MXProfileCreateDomain): events carry the domain as their
    chrome-trace category, so traces group per domain."""

    def __init__(self, name):
        self.name = str(name)

    def new_task(self, name="task"):
        return Task(self, name)

    def new_counter(self, name="counter", value=None):
        return Counter(self, name, value)

    def new_marker(self, name="marker"):
        return Marker(self, name)

    def __repr__(self):
        return f"Domain({self.name!r})"


def _domain_name(domain):
    if domain is None:
        return None
    return getattr(domain, "name", str(domain))


class _Timed:
    """Scoped marker base (reference profiler.py Task/Event/Frame)."""

    def __init__(self, name, category):
        self._name = name
        self._category = category
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        if self._t0 is None:
            return
        dur = (time.perf_counter() - self._t0) * 1e6
        _record(self._name, self._category, self._t0 * 1e6, dur)
        self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Scope(_Timed):
    """Named scope; while active it also tags memory-allocation events on
    this thread (the reference's profiler scope strings in
    storage_profiler alloc names)."""

    def __init__(self, name="<unk>:"):
        super().__init__(name, "scope")

    def start(self):
        super().start()
        stack = getattr(_scope_tls, "stack", None)
        if stack is None:
            stack = _scope_tls.stack = []
        stack.append(self._name)

    def stop(self):
        stack = getattr(_scope_tls, "stack", None)
        if stack and stack[-1] == self._name:
            stack.pop()
        super().stop()


class Task(_Timed):
    def __init__(self, domain=None, name="task"):
        dom = _domain_name(domain)
        super().__init__(name, dom if dom else "task")


class Event(_Timed):
    def __init__(self, name="event"):
        super().__init__(name, "event")


_MARK_SCOPES = {"process": "p", "thread": "t", "global": "g"}


class Marker:
    """Instant marker (reference profiler.py Marker.mark): `ph:"i"` with
    the chrome instant-scope flag derived from mark(scope=...)."""

    def __init__(self, domain=None, name="marker"):
        self._name = name
        self._category = _domain_name(domain) or "marker"

    def mark(self, scope="process"):
        _record(self._name, self._category, time.perf_counter() * 1e6, 0,
                ph="i", scope=_MARK_SCOPES.get(scope, "t"))


class Counter:
    """Numeric counter series (reference profiler.py Counter). increment/
    decrement are atomic: the read-modify-write happens under the module
    lock, so concurrent bumps from serve/batcher threads never lose
    updates."""

    def __init__(self, domain=None, name="counter", value=None):
        dom = _domain_name(domain)
        self._name = f"{dom}::{name}" if dom else name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        with _lock:
            self._value = value
            _counter_sample_locked(self._name, value)

    def increment(self, delta=1):
        with _lock:
            self._value += delta
            _counter_sample_locked(self._name, self._value)

    def decrement(self, delta=1):
        self.increment(-delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self
