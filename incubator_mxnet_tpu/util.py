"""Misc utilities (reference python/mxnet/util.py, 604 LoC).

The reference's util.py mostly manages numpy-shape/array semantics switches
threaded through the C API; here those are process-local flags consumed by
the mxnet.numpy namespace, plus the small filesystem/env helpers user code
imports.
"""
from __future__ import annotations

import collections
import functools
import os
import threading

__all__ = ["makedirs", "set_np_shape", "is_np_shape", "use_np_shape",
           "np_shape", "set_np_array", "is_np_array", "np_array", "use_np",
           "set_np", "reset_np", "getenv", "setenv", "default_array",
           "ENV_VARS", "EnvSpec", "getenv_int", "getenv_bool", "getenv_str"]

_tls = threading.local()


def makedirs(d):
    """Reference util.py makedirs (py2 compat wrapper there; kept for API)."""
    os.makedirs(os.path.expanduser(d), exist_ok=True)


# -- numpy-semantics switches (reference util.py set_np_shape:68 etc.) ------

def _flags():
    if not hasattr(_tls, "np_shape"):
        _tls.np_shape = False
        _tls.np_array = False
    return _tls


def set_np_shape(active):
    """Allow zero-dim/zero-size arrays (reference util.py:68). Under jax
    these are always expressible; the flag only controls legacy-shape
    validation in the NDArray layer."""
    prev = _flags().np_shape
    _flags().np_shape = bool(active)
    return prev


def is_np_shape():
    return _flags().np_shape


def set_np_array(active):
    prev = _flags().np_array
    _flags().np_array = bool(active)
    return prev


def is_np_array():
    return _flags().np_array


class _NpShapeScope:
    def __init__(self, shape=True, array=None):
        self._shape = shape
        self._array = array

    def __enter__(self):
        self._prev_shape = set_np_shape(self._shape)
        if self._array is not None:
            self._prev_array = set_np_array(self._array)
        return self

    def __exit__(self, *exc):
        set_np_shape(self._prev_shape)
        if self._array is not None:
            set_np_array(self._prev_array)


def np_shape(active=True):
    """Context manager (reference util.py np_shape)."""
    return _NpShapeScope(shape=active)


def np_array(active=True):
    return _NpShapeScope(shape=is_np_shape(), array=active)


def use_np_shape(func):
    """Decorator (reference util.py use_np_shape)."""

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        with np_shape(True):
            return func(*args, **kwargs)

    return wrapper


def use_np(func):
    """Decorator enabling both np shape + array semantics
    (reference util.py use_np)."""

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        with _NpShapeScope(shape=True, array=True):
            return func(*args, **kwargs)

    return wrapper


def set_np(shape=True, array=True):
    set_np_shape(shape)
    set_np_array(array)


def reset_np():
    set_np(False, False)


def getenv(name):
    """Reference util.py getenv -> MXGetEnv."""
    return os.environ.get(name)


def setenv(name, value):
    os.environ[name] = value


# -- environment-variable registry ------------------------------------------
#
# Every MXNET_*/MXTPU_* knob the package reads is declared here once, with
# its type, default, and doc, and read only through getenv_int/getenv_bool/
# getenv_str below.  tools/mxlint enforces this (rules EV01/EV02) and
# tools/diagnose.py prints the table with live values.  The reference
# framework documented its env vars in docs/faq/env_var.md by hand; keeping
# the registry in code makes the doc impossible to forget.

EnvSpec = collections.namedtuple("EnvSpec", ["default", "kind", "doc"])

ENV_VARS = collections.OrderedDict([
    ("MXNET_OPTIMIZER_AGGREGATION_SIZE", EnvSpec(4, "int",
     "Max parameters fused into one multi-tensor optimizer dispatch by "
     "gluon.Trainer; <=1 restores per-tensor updates.")),
    ("MXNET_KVSTORE_BIGARRAY_BOUND", EnvSpec(1000 * 1000, "int",
     "Element count at/above which a kvstore array takes the "
     "ownership-sharded wire (reference kvstore_dist.h bigarray bound).")),
    ("MXNET_KVSTORE_FLATPACK_BOUND", EnvSpec(32 << 20, "int",
     "Flat-pack bucket byte cap for kvstore.pushpull_list gradient "
     "aggregation.")),
    ("MXNET_KVSTORE_BIND_ADDR", EnvSpec("", "str",
     "Interface the dist_async parameter server binds to; empty (default) "
     "binds the coordinator-facing interface only — never 0.0.0.0 unless "
     "set explicitly.")),
    ("MXNET_KVSTORE_ASYNC_ADDR", EnvSpec("", "str",
     "Elastic-join endpoint for the dist_async parameter server as "
     "'host:port token'. When set, a single-process worker connects "
     "directly (no jax.distributed rendezvous) and is assigned a rank by "
     "the server — the replacement-worker path after a kill -9.")),
    ("MXNET_KVSTORE_CONNECT_TIMEOUT", EnvSpec(10, "int",
     "Seconds an AsyncClient waits for one TCP connect + nonce exchange "
     "to the dist_async server before retrying.")),
    ("MXNET_KVSTORE_CALL_TIMEOUT", EnvSpec(60, "int",
     "Seconds an AsyncClient waits for the reply to one RPC frame before "
     "treating the server as wedged and retrying over a fresh "
     "connection.")),
    ("MXNET_KVSTORE_RETRIES", EnvSpec(4, "int",
     "Reconnect/retry attempts (per call and per connect) against a dead "
     "or wedged dist_async server before raising MXNetError.")),
    ("MXNET_KVSTORE_RETRY_BACKOFF_MS", EnvSpec(100, "int",
     "Initial retry backoff in milliseconds; doubles per attempt "
     "(exponential, capped at 10s).")),
    ("MXNET_HEARTBEAT_INTERVAL", EnvSpec(2, "int",
     "Seconds between background worker heartbeats to the dist_async "
     "server's liveness registry.")),
    ("MXNET_DEAD_NODE_TIMEOUT", EnvSpec(30, "int",
     "Seconds without a heartbeat after which the dist_async server "
     "reports a worker dead (get_dead_nodes default; reference "
     "kvstore_dist.h:121 node timeout).")),
    ("MXNET_STRAGGLER_LAG", EnvSpec(100, "int",
     "Heartbeat-reported step lag behind the fastest worker at/above "
     "which a worker is counted a straggler.")),
    ("MXNET_CKPT_QUEUE", EnvSpec(2, "int",
     "Bounded write-behind queue depth of fault.AsyncCheckpointManager; "
     "when full the OLDEST pending snapshot is dropped (newest state "
     "wins) so a slow disk never stalls the train loop.")),
    ("MXNET_FAULT_INJECT", EnvSpec("", "str",
     "Test-suite only: fault-injection spec 'site@n:action[,...]' where "
     "action is kill, drop, or delay=SECONDS — e.g. 'push@5:kill' kills "
     "the process at the 5th kvstore push, 'frame@3:drop' drops the 3rd "
     "wire frame. Empty disables injection.")),
    ("MXNET_COMPILE_WARN_THRESHOLD", EnvSpec(8, "int",
     "Compiles of the same jit key after which the profiler warns about "
     "a likely recompile loop.")),
    ("MXNET_EXEC_CACHE_DIR", EnvSpec("", "str",
     "Persistent executable-cache directory (compile_cache.py): AOT-"
     "compiled XLA executables from the four tracked jit choke points "
     "(op registry, fused optimizer, kvstore flat-pack, serving) are "
     "serialized here and deserialized by later processes, so a fleet "
     "replica cold-starts without recompiling. Empty (default) disables "
     "the disk tier; the in-memory LRU is always on. Distinct from "
     "jax's own compilation cache (JAX_COMPILATION_CACHE_DIR, else "
     "<checkout>/.jax_cache), which still pays tracing+lowering per "
     "process.")),
    ("MXNET_EXEC_CACHE_SIZE", EnvSpec(1024, "int",
     "Entry capacity of the process-wide in-memory executable LRU shared "
     "by all compile_cache.cached_jit call sites; replaces serve's "
     "per-predictor hard executable cap and the per-op FIFO memos as THE "
     "eviction policy.")),
    ("MXNET_EXEC_CACHE_DISK_BYTES", EnvSpec(2 << 30, "int",
     "Byte budget for MXNET_EXEC_CACHE_DIR; after a write pushes "
     "occupancy past it, oldest entries (mtime order) are evicted. "
     "<=0 disables the bound.")),
    ("MXNET_SHARDLINT", EnvSpec(False, "bool",
     "Enable shardlint graph capture: the jit choke points "
     "(compile_cache.cached_jit, profiler.track_jit, tune.tuned_call) and "
     "the partition-rule matcher snapshot jaxprs/coverage reports into "
     "shardlint.captures() for the tools/shardlint rule passes "
     "(SL01-SL05). Off (default), every hook is a cached boolean check on "
     "a once-per-signature path — zero steady-state overhead.")),
    ("MXNET_SHARDLINT_CAPTURES", EnvSpec(256, "int",
     "Bound on the shardlint capture buffer; once full the OLDEST capture "
     "is dropped (counted in shardlint.stats()['dropped']).")),
    ("MXNET_SHARDLINT_CORPUS", EnvSpec("", "str",
     "Comma-separated subset of the tools/shardlint offline model corpus "
     "to trace (see tools.shardlint.corpus.entries()); empty (default) "
     "traces every registered entry.")),
    ("MXNET_HOME", EnvSpec("~/.mxnet", "str",
     "Data directory for downloaded model-zoo parameter files.")),
    ("MXNET_GLUON_REPO", EnvSpec(
     "https://apache-mxnet.s3-accelerate.dualstack.amazonaws.com/", "str",
     "Base URL for gluon model-zoo downloads.")),
    ("MXTPU_NO_NATIVE", EnvSpec(False, "bool",
     "Disable the C accelerators for recordio/image packing and fall "
     "back to pure python.")),
    ("MXNET_TUNE", EnvSpec(True, "bool",
     "Enable the kernel autotuner (tune.py): per-(kernel, shape, dtype, "
     "device) timed selection between hand Pallas kernels and the plain "
     "XLA composition. Off, every tuned_call site runs its XLA "
     "fallback.")),
    ("MXNET_TUNE_SAMPLES", EnvSpec(3, "int",
     "Timed repetitions per autotuner candidate (best-of); the first, "
     "untimed call absorbs compilation.")),
    ("MXTPU_TUNE_INTERPRET", EnvSpec(False, "bool",
     "Offer interpret-mode Pallas candidates to the autotuner off-TPU. "
     "Test-suite only: interpret mode always loses a fair timing race, "
     "so off-TPU candidate sets are empty unless this is set.")),
    ("MXTPU_FUSED_BLOCK", EnvSpec(True, "bool",
     "Route gluon ResNet residual units through the fused "
     "conv+BN(+add)+ReLU ops (autotuned; the XLA candidate keeps the "
     "unfused numerics). Off restores the layer-by-layer oracle path.")),
    ("MXTPU_FP32_MATMUL", EnvSpec("strict", "str",
     "fp32 matmul precision: 'strict' (MXNet semantics, fp32 "
     "accumulate), 'fast' (bf16_3x), or 'fastest' (plain bf16).")),
    ("MXTPU_TEST_PLATFORM", EnvSpec("cpu", "str",
     "Test-suite only: jax platform the suite pins itself to.")),
    ("MXTPU_TEST_SEED", EnvSpec(0, "int",
     "Test-suite only: base RNG seed for the randomized operator tests.")),
    ("MXNET_STEP_ATTRIBUTION", EnvSpec(False, "bool",
     "Enable step-time attribution: profiler.span(phase) wired into "
     "TrainStep.run_epoch / Trainer.step / the serve batcher books "
     "per-phase ms/step (input_wait, h2d, compute, collective, "
     "optimizer, ckpt_snapshot, queue_wait) into dumps(), nested "
     "chrome-trace spans, and mxnet_step_phase_ms histograms, and "
     "TrainStep's compute span WAITS for the loss so that its time is "
     "real. Always there, gate or no gate: while a jax profiler session "
     "records, every span is a trace annotation mx:<phase> on the "
     "device trace's clock. Off (the default) and with no session, the "
     "span API returns a shared no-op and the hot paths do zero "
     "bookkeeping.")),
    ("MXNET_FLIGHT_RECORDER", EnvSpec("", "str",
     "Directory for the crash flight recorder. When set, fault.py keeps "
     "a bounded ring of recent step records/events and dumps it "
     "atomically as JSON on SIGUSR1, on a FaultInjector trip, and on an "
     "unhandled exception in run_epoch. Empty (the default) disables "
     "the recorder entirely.")),
    ("MXNET_FLIGHT_RECORDER_SIZE", EnvSpec(256, "int",
     "Flight-recorder ring capacity: how many recent step records and "
     "events the postmortem dump retains (oldest dropped first).")),
    ("MXNET_FLEET_OBS", EnvSpec(False, "bool",
     "Enable the fleet observability plane (fleetobs.py): each rank "
     "attaches a bounded metric snapshot (phase histogram deltas, MFU, "
     "exec-cache/tune counters, top compiler cost records) to its "
     "authenticated kvstore heartbeat; the coordinator folds them into a "
     "FleetRegistry serving fleet-wide /metrics, /fleet, and /alerts and "
     "evaluates the SLO burn-rate engine. Off (the default), the "
     "heartbeat payload is byte-identical to the non-fleet wire and no "
     "snapshot work happens.")),
    ("MXNET_FLEET_SNAPSHOT_INTERVAL", EnvSpec(1, "int",
     "Attach a fleet snapshot to every Nth heartbeat (>=1). Raising it "
     "bounds per-beat wire overhead on large fleets; intermediate beats "
     "stay plain v2 heartbeats.")),
    ("MXNET_FLEET_SLO_PATH", EnvSpec("", "str",
     "Path to a fleet SLO spec file (one spec per line, '#' comments; "
     "grammar: 'p99(queue_wait) < 50ms', 'mfu > 0.3', "
     "'straggler_lag < 1.5x'). Empty (the default) loads the built-in "
     "straggler_lag spec only.")),
    ("MXNET_FLEET_SLO_INTERVAL", EnvSpec(5, "int",
     "Seconds between SLO burn-rate evaluations at the coordinator; the "
     "short burn window is one interval, the long window five.")),
    ("MXNET_FLEET_PROFILE_MAX_STEPS", EnvSpec(50, "int",
     "Upper bound on the step count a remote-profile control op may "
     "request from a rank; larger requests are clamped.")),
    ("MXNET_FLEET_PROFILE_MAX_SECONDS", EnvSpec(30, "int",
     "Wall-clock cap on one remote-profile session; the rank stops and "
     "ships whatever it captured when the cap expires before N steps.")),
    ("MXNET_FLEET_PROFILE_MAX_BYTES", EnvSpec(4 << 20, "int",
     "Byte cap on a shipped remote-profile trace segment; oldest events "
     "are dropped until the JSON payload fits, and the coordinator "
     "refuses oversized pushes outright.")),
    ("MXNET_KVSTORE_RETRY_JITTER", EnvSpec(True, "bool",
     "Randomize AsyncClient retry backoff by a uniform [0.5, 1.5) "
     "factor so a fleet of workers does not retry in lockstep after a "
     "coordinator restart (thundering herd). Off restores the "
     "deterministic doubling schedule (tests that assert exact retry "
     "timing).")),
    ("MXNET_ROUTER_DEADLINE_MS", EnvSpec(1000, "int",
     "Default end-to-end deadline for one Router.request, covering "
     "every retry and hedge; a request that cannot complete inside it "
     "fails with a retryable deadline error.")),
    ("MXNET_ROUTER_RETRIES", EnvSpec(3, "int",
     "Retry budget per routed request on RETRYABLE failures only "
     "(connect error, 503 shed); application errors (400/500) are "
     "never retried.")),
    ("MXNET_ROUTER_RETRY_BACKOFF_MS", EnvSpec(10, "int",
     "Initial router retry backoff; doubles per attempt with uniform "
     "[0.5, 1.5) jitter, capped at 1s and always bounded by the "
     "request deadline.")),
    ("MXNET_ROUTER_HEDGE_DELAY_MS", EnvSpec(0, "int",
     "Hedged-request trigger: a second replica is tried when the first "
     "attempt has not answered after this long. 0 (the default) "
     "derives the delay from the router's observed p99 latency "
     "(50ms floor until enough samples exist).")),
    ("MXNET_ROUTER_BREAKER_FAILURES", EnvSpec(5, "int",
     "Consecutive connect/timeout failures that open a replica's "
     "circuit breaker (the replica stops receiving traffic until a "
     "half-open probe succeeds). 503 sheds do NOT count — a shedding "
     "replica is alive.")),
    ("MXNET_ROUTER_BREAKER_COOLDOWN_MS", EnvSpec(2000, "int",
     "How long an open circuit breaker waits before letting one "
     "half-open probe request through; the probe's outcome closes or "
     "re-opens the breaker.")),
    ("MXNET_ROUTER_REFRESH_MS", EnvSpec(500, "int",
     "Router discovery period: how often the replica table is "
     "re-pulled from the coordinator's serve registry.")),
    ("MXNET_ROLLOUT_WAVE_SIZE", EnvSpec(1, "int",
     "Replicas updated per rollout wave; the SLO gate is evaluated "
     "between waves, so smaller waves bound the blast radius of a bad "
     "generation.")),
    ("MXNET_ROLLOUT_SLO_GATE", EnvSpec(True, "bool",
     "Gate rollout waves on the fleet SLO engine: any alert firing "
     "after a wave settles triggers automatic rollback of every "
     "already-updated replica. Off, waves proceed unconditionally.")),
    ("MXNET_ROLLOUT_SETTLE_MS", EnvSpec(200, "int",
     "Post-wave settle time before the SLO gate is consulted, so the "
     "new generation's traffic is actually represented in the "
     "evaluated window.")),
    ("MXNET_SERVE_DRAIN_TIMEOUT", EnvSpec(30, "int",
     "Seconds a draining ModelServer (SIGTERM / rollout weight swap) "
     "waits for in-flight batches to flush before forcing shutdown; "
     "new requests get fast 503 + Retry-After for the duration.")),
    ("MXNET_DECODE_SLOTS", EnvSpec(8, "int",
     "Decode slot-batch width: the ONE fixed shape the continuous-"
     "batching decode executable is compiled for. Sequences are "
     "admitted into and retired from these slots every step; changing "
     "it is a recompile.")),
    ("MXNET_DECODE_QUEUE", EnvSpec(64, "int",
     "Bounded decode admission queue; a stream submitted beyond it is "
     "shed with a retryable Overloaded (503) instead of queueing into "
     "collapse.")),
    ("MXNET_DECODE_MAX_NEW_TOKENS", EnvSpec(32, "int",
     "Default per-stream generation cap when the request does not set "
     "max_new_tokens; also sizes the KV pages claimed at admission.")),
    ("MXNET_DECODE_QUEUE_BOUND_MS", EnvSpec(0, "int",
     "Projected-queue-wait admission bound in ms: shed (503 + "
     "Retry-After) when p95 of recent admission waits scaled by the "
     "current queue depth breaches it — the queue-wait-histogram "
     "admission signal. 0 disables projection shedding (the bounded "
     "queue still sheds).")),
    ("MXNET_KV_PAGE_SIZE", EnvSpec(16, "int",
     "Token rows per KV page. Internal fragmentation is bounded by "
     "page_size-1 rows per sequence; the ragged paged-attention kernel "
     "walks pages of exactly this many rows.")),
    ("MXNET_KV_PAGES", EnvSpec(128, "int",
     "KV page pool capacity shared by all decode slots. Exhaustion "
     "holds the admission queue (retires free pages) and sheds once "
     "the queue itself fills.")),
    ("MXNET_KV_PAGES_PER_SEQ", EnvSpec(8, "int",
     "Per-sequence page-table width (max pages one stream may own). "
     "Requests whose prompt+max_new_tokens exceed it are rejected as "
     "NON-retryable — no replica can serve them.")),
    ("MXNET_PREFIX_CACHE", EnvSpec(True, "bool",
     "Enable the copy-on-write prefix cache on serving engines that "
     "construct one by default (PrefillEngine, disagg-role "
     "ModelServers). A cached prefix is shared read-only by any number "
     "of streams; only the divergent tail page is ever copied.")),
    ("MXNET_PREFIX_CACHE_PAGES", EnvSpec(64, "int",
     "Capacity of the prefix cache in KV pages. Inserts beyond it "
     "evict least-recently-used cached pages, and ONLY pages no live "
     "stream references (allocator refcount down to the cache's own "
     "hold); when nothing is evictable the insert is skipped.")),
    ("MXNET_DISAGG_ROLE", EnvSpec("both", "str",
     "Serving replica role advertised to the ServeRegistry: 'prefill' "
     "(chunked prefill + KV-page export only), 'decode' (token "
     "generation from shipped pages), or 'both' (the PR-13 colocated "
     "engine). The router places prefill traffic on prefill-capable "
     "replicas and decode streams on decode-capable ones.")),
    ("MXNET_DISAGG_PREFILL_CHUNK", EnvSpec(16, "int",
     "Token rows per chunked-prefill step. Long prompts are processed "
     "in fixed chunks of this many positions so a decode-colocated "
     "replica interleaves decode steps between chunks instead of "
     "stalling a whole prompt's worth of prefill; one executable "
     "serves every chunk (start/length are traced scalars).")),
    ("MXNET_DISAGG_SHIP_TTL", EnvSpec(60, "int",
     "Seconds an exported KV-page bundle survives in the "
     "coordinator's page store awaiting pickup by the target decode "
     "replica. Expired bundles are dropped at the next store access; "
     "a consumer arriving late re-runs prefill instead of reading "
     "stale pages.")),
    ("MXTPU_PP_SCHEDULE", EnvSpec("gpipe", "str",
     "Pipeline-parallel microbatch schedule for the composed train "
     "step: 'gpipe' (all-forward then the transposed all-backward), "
     "'1f1b' (one-forward-one-backward steady state with bounded "
     "in-flight activations), 'interleaved' (v virtual chunks per "
     "rank, bubble ~1/v of 1F1B's), or 'zb1' (ZB-H1: backward split "
     "into input-grad and weight-grad half-passes, W-passes filling "
     "the cooldown). An explicit schedule= argument overrides it.")),
    ("MXTPU_PP_VSTAGES", EnvSpec(2, "int",
     "Virtual pipeline chunks per rank (v) for the 'interleaved' "
     "schedule — block params are (v, S)-stacked and rank r runs "
     "virtual stages c*S+r. Ignored by other schedules; an explicit "
     "n_chunks= argument overrides it.")),
    ("MXNET_PP_OFFLOAD", EnvSpec(False, "bool",
     "Offload per-(stage, microbatch) saved activations to pinned "
     "host memory inside the pipelined train step (jax.checkpoint "
     "offload policy on the stage-input residual): per-stage live "
     "HBM is bounded by the in-flight transfer window instead of "
     "the schedule depth, at the price of D2H/H2D traffic the "
     "schedule hides under compute. Composes with MXNET_REMAT none/"
     "full only. Publishes d2h_bytes / offload_wait_ms_per_step "
     "through the profiler counter registry.")),
    ("MXNET_REMAT", EnvSpec("none", "str",
     "Per-stage activation rematerialization policy for pipelined "
     "train steps: 'none' (store), 'dots_saveable' (jax.checkpoint "
     "keeping matmul outputs), or 'full' (recompute everything). "
     "Numerics are bit-identical across policies; only the "
     "memory/recompute trade-off moves.")),
    ("MXNET_SPEC_DECODE", EnvSpec(False, "bool",
     "Enable speculative decoding in DecodeScheduler: a host-side "
     "draft proposes tokens and ONE fixed-shape batched verify "
     "executable scores them per iteration (serve/spec_decode.py). "
     "Greedy outputs are bit-identical to plain decode; this is "
     "purely a throughput knob.")),
    ("MXNET_SPEC_K", EnvSpec(4, "int",
     "Maximum draft tokens proposed per stream per speculative "
     "iteration (the verify executable's width is k+1 and is baked "
     "into its compiled shape). Per-stream depth adapts below this "
     "cap when MXNET_SPEC_ADAPT is on.")),
    ("MXNET_SPEC_ADAPT", EnvSpec(True, "bool",
     "Adapt each stream's draft depth to its measured accept rate: "
     "shrink toward 1 below MXNET_SPEC_ACCEPT_FLOOR_PCT, regrow "
     "toward MXNET_SPEC_K at sustained near-full acceptance. Off: "
     "every stream always proposes MXNET_SPEC_K tokens.")),
    ("MXNET_SPEC_ACCEPT_FLOOR_PCT", EnvSpec(50, "int",
     "Accept-rate floor (percent) for adaptive speculation depth: "
     "below it a stream's k shrinks by one per iteration, bounding "
     "wasted verify work when the draft diverges from the target.")),
    ("MXNET_ROUTER_SLO_SPLIT", EnvSpec(False, "bool",
     "Rank routing candidates by SLO headroom instead of raw load: "
     "prefill placements by TTFT-SLO headroom (MXNET_ROUTER_TTFT_"
     "SLO_MS minus the replica's beaten ttft_p99_ms) and decode "
     "placements by inter-token-SLO headroom, with kv_pages_free as "
     "the tiebreak. Off: dedicated-role-first / most-free-pages "
     "ordering.")),
    ("MXNET_ROUTER_TTFT_SLO_MS", EnvSpec(500, "int",
     "Time-to-first-token SLO target (ms) for the prefill tier's "
     "SLO-split placement ranking.")),
    ("MXNET_ROUTER_TOKEN_SLO_MS", EnvSpec(100, "int",
     "Inter-token latency SLO target (ms) for the decode tier's "
     "SLO-split placement ranking.")),
    ("MXNET_REQTRACE", EnvSpec(False, "bool",
     "Request-scoped tracing across the serving plane "
     "(serve/reqtrace.py): mint a trace context at the router, "
     "propagate it via the X-MXNET-Trace header and the kvstore v2 "
     "wire envelope, and book per-hop chrome-trace spans plus a TTFT "
     "budget breakdown on the /generate done row. Off (default): "
     "zero records, wire frames byte-identical.")),
    ("MXNET_REQTRACE_SAMPLE", EnvSpec(1000, "int",
     "Head-based sampling rate for request tracing, in per-mille "
     "(1000 = trace every request). Unsampled requests still carry "
     "a trace id for tail-exemplar promotion on error/SLO breach, "
     "but emit no spans.")),
    ("MXNET_REQTRACE_RING", EnvSpec(64, "int",
     "Capacity of each request-trace ring (recent sampled requests "
     "and error/SLO-breach exemplars), served at /debugz/requests. "
     "Floored at 4.")),
    ("MXNET_MXSAN", EnvSpec(False, "bool",
     "Witness-based concurrency sanitizer (mxsan.py): lock factories "
     "return instrumented wrappers that record per-thread acquisition "
     "orderings, blocking calls made under a lock, and re-entry on "
     "non-reentrant locks; tools/mxsan cross-checks the observed edges "
     "against tools/mxlint/lock_order.py and reports AB/BA cycles "
     "before they hang. Off (default): factories hand back the raw "
     "stdlib primitives — zero records, zero wrappers.")),
    ("MXNET_MXSAN_RING", EnvSpec(4096, "int",
     "Capacity of the mxsan witness event ring; once full the OLDEST "
     "event is dropped (counted in mxsan.stats()['dropped']). "
     "Floored at 64.")),
    ("MXNET_MXSAN_LOG", EnvSpec("", "str",
     "When set (and MXNET_MXSAN is on), mxsan writes its witness log "
     "(events + observed edge table) to this path as JSON at interpreter "
     "exit, for offline replay via `python -m tools.mxsan <path>`.")),
])

_FALSY = frozenset(("", "0", "false", "off", "no"))


def _spec(name):
    try:
        return ENV_VARS[name]
    except KeyError:
        from .base import MXNetError
        raise MXNetError(
            f"environment variable {name!r} is not declared in "
            f"util.ENV_VARS; add it there with a default and doc")


def getenv_int(name):
    """Declared-default int read of an ENV_VARS entry; an unparseable
    value falls back to the default rather than crashing startup."""
    spec = _spec(name)
    raw = os.environ.get(name)
    if raw is None:
        return spec.default
    try:
        return int(raw)
    except ValueError:
        return spec.default


def getenv_bool(name):
    """Declared-default bool read; '', '0', 'false', 'off', 'no' (any
    case) are False, everything else set is True."""
    spec = _spec(name)
    raw = os.environ.get(name)
    if raw is None:
        return spec.default
    return raw.strip().lower() not in _FALSY


def getenv_str(name):
    """Declared-default string read of an ENV_VARS entry."""
    spec = _spec(name)
    raw = os.environ.get(name)
    return spec.default if raw is None else raw


def default_array(source_array, ctx=None, dtype=None):
    """Array in the currently-active frontend semantics (reference
    util.py default_array)."""
    if is_np_array():
        from . import numpy as np_mod
        return np_mod.array(source_array, dtype=dtype)
    from . import nd
    return nd.array(source_array, dtype=dtype)
