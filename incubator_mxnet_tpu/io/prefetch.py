"""Device-side input pipeline: double-buffered host->HBM prefetch.

Reference: src/io/iter_prefetcher.h:47 ``PrefetcherIter`` — a
dmlc::ThreadedIter double-buffer hiding batch N+1's decode+copy behind
batch N's compute. The reference's buffer stops at host memory: the
NDArray->device copy still serializes with the step. Here the background
stage issues the host->HBM transfer itself — ``jax.device_put`` is async
(it returns immediately with a future-backed Array), so batch N+1's DMA
overlaps batch N's XLA program. Given a mesh, placement uses a
``NamedSharding`` over the data axis, so multichip consumers (TrainStep,
``parallel.train.shard_batch`` users) receive pre-placed shards and never
pay a second device_put.

Telemetry (the data-stall diagnosis surface): every consumer get publishes

- ``input_wait_ms_per_step`` — time the step blocked waiting for input
  (0 in steady state means the pipeline keeps the chip fed)
- ``prefetch_depth``        — batches ready in the buffer after the get
  (pinned at 0 means the run is input-bound)
- ``h2d_bytes``             — cumulative bytes staged to the device

through the profiler counter registry, so a stalled run is diagnosable
from ``profiler.dumps()`` or the ``/metrics`` Prometheus scrape alone.

In a recorded jax profiler trace the consumer's wait is the span
``mx:input_wait`` on the consumer's thread and the worker's placement of a
batch ``mx:prefetch_place`` on the worker's own line, beside the step: both
are trace annotations, there whenever a session records and never booked.
``MXNET_STEP_ATTRIBUTION`` adds nothing here; the booked ``input_wait`` of
its phase table is ``TrainStep.run_epoch``'s, round this iterator's
``next``.
"""
from __future__ import annotations

import queue as _queue_mod
import threading
import time

import numpy as _np

from ..base import MXNetError

__all__ = ["DevicePrefetcher", "HostOffloader", "prefetch_to_device"]


def prefetch_to_device(iterator, size=2, mesh=None, axis="dp", device=None,
                       skip_batches=0):
    """Wrap a host batch iterator in a background device-placement stage.

    iterator: anything iterable yielding batches — NDArrays, (data, label)
        tuples/lists, dicts, numpy arrays, or io.DataBatch objects. Array
        leaves are placed on device asynchronously; non-array leaves pass
        through untouched.
    size:     queue depth (2 = classic double buffering).
    skip_batches: discard this many source batches on the worker thread
        WITHOUT device placement — the mid-epoch-exact resume fast-forward
        (fault.AsyncCheckpointManager stores the consumed-batch cursor;
        passing it here replays an epoch from the exact next batch). The
        skipped batches still advance :attr:`cursor`.
    mesh/axis: place leaves with NamedSharding(mesh, P(axis)) — pre-sharded
        input for SPMD consumers (TrainStep skips its own device_put on
        shards that already carry this sharding).
    device:   explicit jax device target (mutually exclusive with mesh).
        With neither, numpy leaves go to the default device and
        already-committed arrays are left in place (their transfer was
        issued on the prefetch thread, which is the point).

    Returns a :class:`DevicePrefetcher` — an iterator that preserves the
    source order and values bit-for-bit, supports early abandonment via
    ``close()`` (the source iterator's cleanup runs on the worker thread,
    so a generator source's ``finally`` — e.g. the DataLoader shm drain —
    still executes), and publishes data-stall counters to the profiler.
    """
    return DevicePrefetcher(iterator, size=size, mesh=mesh, axis=axis,
                            device=device, skip_batches=skip_batches)


class DevicePrefetcher:
    """Single background thread + bounded FIFO queue: the host stages of
    the source iterator (decode, batchify, shm copy-out) AND the H2D issue
    run off the consumer thread; order is preserved by construction."""

    def __init__(self, iterator, size=2, mesh=None, axis="dp", device=None,
                 skip_batches=0):
        if size < 1:
            raise MXNetError("prefetch size must be >= 1")
        if mesh is not None and device is not None:
            raise MXNetError("mesh and device are mutually exclusive")
        if skip_batches < 0:
            raise MXNetError("skip_batches must be >= 0")
        self._src = iter(iterator)
        self._skip = int(skip_batches)
        # source batches consumed, INCLUDING skipped ones: the data-iterator
        # position a checkpoint records for mid-epoch-exact resume
        self.cursor = int(skip_batches)
        self._sharding = None
        self._device = device
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._sharding = NamedSharding(mesh, P(axis))
        self.size = size
        self._queue = _queue_mod.Queue(maxsize=size)
        self._stop = threading.Event()
        self._done = False
        # consumer-side telemetry: written only by the consuming thread
        # (the worker communicates through the queue alone), so no lock
        self.batches = 0
        self.bytes_total = 0
        self.last_wait_ms = 0.0
        self.wait_ms_total = 0.0
        self._counters = None
        self._thread = threading.Thread(target=self._worker,
                                        name="mxtpu-device-prefetch",
                                        daemon=True)
        self._thread.start()

    # -- producer ----------------------------------------------------------
    def _worker(self):
        from .. import profiler as _prof
        src = self._src
        try:
            # resume fast-forward: burn the already-consumed prefix off the
            # worker thread, no placement cost, before the first real batch
            for _ in range(self._skip):
                if self._stop.is_set():
                    return
                try:
                    next(src)
                except StopIteration:
                    self._offer(("done", None, 0))
                    return
            while not self._stop.is_set():
                try:
                    batch = next(src)
                except StopIteration:
                    self._offer(("done", None, 0))
                    return
                with _prof.span("prefetch_place", book=False):
                    placed, nbytes = self._place(batch)
                if not self._offer(("ok", placed, nbytes)):
                    return                      # closed while queue full
        except BaseException as e:              # noqa: BLE001 — re-raised
            self._offer(("err", e, 0))          # in the consumer
        finally:
            # the worker owns the source: closing it HERE runs a generator
            # source's finally blocks (the DataLoader shm drain) on the
            # thread the generator actually executed on
            close = getattr(src, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:               # noqa: BLE001
                    pass

    def _offer(self, item):
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except _queue_mod.Full:
                continue
        return False

    # -- placement ---------------------------------------------------------
    def _place(self, batch):
        nbytes = [0]
        return self._place_tree(batch, nbytes), nbytes[0]

    def _place_tree(self, x, nbytes):
        from ..ndarray.ndarray import NDArray
        from .io import DataBatch
        if type(x) is NDArray:
            return NDArray(self._place_leaf(x._data, nbytes))
        if isinstance(x, NDArray):
            return x        # sparse containers: multi-buffer, pass through
        if isinstance(x, DataBatch):
            out = DataBatch(
                data=self._place_tree(x.data, nbytes),
                label=self._place_tree(x.label, nbytes),
                pad=x.pad, index=x.index, bucket_key=x.bucket_key,
                provide_data=x.provide_data, provide_label=x.provide_label)
            return out
        if isinstance(x, (tuple, list)):
            return type(x)(self._place_tree(v, nbytes) for v in x)
        if isinstance(x, dict):
            return {k: self._place_tree(v, nbytes) for k, v in x.items()}
        if isinstance(x, _np.ndarray) or hasattr(x, "devices"):
            return self._place_leaf(x, nbytes)
        return x

    def _place_leaf(self, a, nbytes):
        import jax
        import jax.numpy as jnp
        if self._sharding is not None:
            placed = jax.device_put(a, self._sharding)
        elif self._device is not None:
            placed = jax.device_put(a, self._device)
        elif hasattr(a, "devices"):
            # already device-resident: its H2D was issued by whatever
            # constructed it — which ran on THIS thread, inside next(src)
            placed = a
        else:
            placed = jnp.asarray(a)
        try:
            nbytes[0] += int(placed.nbytes)
        except (TypeError, AttributeError):
            pass
        return placed

    # -- consumer ----------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        from .. import profiler
        if self._done:
            raise StopIteration
        t0 = time.perf_counter()
        with profiler.span("input_wait", book=False):
            while True:
                try:
                    kind, payload, nbytes = self._queue.get(timeout=1.0)
                    break
                except _queue_mod.Empty:
                    if not self._thread.is_alive() and self._queue.empty():
                        self._done = True
                        raise MXNetError(
                            "device prefetch worker died without a "
                            "sentinel")
        wait_ms = (time.perf_counter() - t0) * 1e3
        if kind != "ok":
            self._done = True
            self._thread.join(timeout=5)
            if kind == "err":
                raise payload
            raise StopIteration
        self.batches += 1
        self.cursor += 1
        self.bytes_total += nbytes
        self.last_wait_ms = wait_ms
        self.wait_ms_total += wait_ms
        self._publish(wait_ms)
        return payload

    def _publish(self, wait_ms):
        from .. import profiler
        if not profiler.is_running():
            return
        if self._counters is None:
            self._counters = (
                profiler.Counter(name="input_wait_ms_per_step"),
                profiler.Counter(name="prefetch_depth"),
                profiler.Counter(name="h2d_bytes"))
        self._counters[0].set_value(round(wait_ms, 3))
        self._counters[1].set_value(self._queue.qsize())
        self._counters[2].set_value(self.bytes_total)

    def stats(self):
        """Always-readable snapshot (the counters above require a running
        profiler; tests and bench read this directly)."""
        return {"batches": self.batches, "h2d_bytes": self.bytes_total,
                "last_wait_ms": self.last_wait_ms,
                "wait_ms_total": self.wait_ms_total,
                "depth": self._queue.qsize(), "size": self.size,
                "cursor": self.cursor}

    def state(self):
        """Checkpointable position: pass ``state()['cursor']`` back as
        ``skip_batches`` over the same source to resume mid-epoch exactly
        (no skipped, no repeated batches)."""
        return {"cursor": self.cursor}

    # -- lifecycle ---------------------------------------------------------
    def close(self):
        """Stop the worker and drop buffered batches. Safe to call twice.
        Early abandonment (break out of the consuming loop) MUST end here
        (or via GC) so the source's cleanup runs — for the DataLoader shm
        protocol that is what unlinks in-flight segments."""
        self._done = True
        self._stop.set()
        # drain so a worker blocked on a full queue observes the stop
        self._drain()
        if self._thread.is_alive():
            self._thread.join(timeout=10)
        self._drain()       # anything offered between drain and join

    def _drain(self):
        try:
            while True:
                self._queue.get_nowait()
        except _queue_mod.Empty:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:                       # noqa: BLE001 — interpreter
            pass                                # shutdown: queue/thread gone


class HostOffloader:
    """The DevicePrefetcher's machinery run in REVERSE: a bounded window of
    async device->host copies of live activations, prefetched BACK to the
    device ahead of their consumer. Reference: the MXNet dependency engine
    hiding D2H/H2D under compute via dependency-ordered async copies —
    here ``jax.device_put`` to a host ``memory_kind`` is the async copy and
    the bounded window is the double buffer.

    ``put(key, a)`` issues the D2H and returns immediately; when the
    in-flight window is full it first BLOCKS on the oldest transfer (that
    wait is the ``offload_wait_ms_per_step`` stall the counters surface —
    0 in steady state means the copies hide under compute). ``prefetch``
    issues the H2D back without blocking; ``get`` returns the
    device-resident array, waiting only if the prefetch hasn't landed.
    Round trips are bit-identical by construction (same buffer, moved).

    Telemetry, through the same profiler counter registry as the input
    pipeline (``profiler.dumps()`` / the ``/metrics`` scrape):

    - ``d2h_bytes``               — cumulative bytes parked on the host
    - ``offload_wait_ms_per_step`` — consumer time blocked on the window

    On backends without addressable host memory spaces the offloader
    degrades to an on-device ring (``host_backed`` False): the window
    accounting and telemetry stay live, the copies become no-ops.
    """

    def __init__(self, window=2):
        if window < 1:
            raise MXNetError("offload window must be >= 1")
        self.window = window
        self._host = {}           # key -> host-resident array
        self._back = {}           # key -> device-put-back array (prefetch)
        self._order = []          # FIFO of in-flight D2H keys
        self._shardings = {}      # key -> original device sharding
        self.d2h_bytes = 0
        self.h2d_bytes = 0
        self.last_wait_ms = 0.0
        self.wait_ms_total = 0.0
        self.puts = 0
        self._counters = None
        self._host_kind = self._probe_host_kind()

    @staticmethod
    def _probe_host_kind():
        import jax
        kinds = {m.kind for d in jax.local_devices()
                 for m in d.addressable_memories()}
        for kind in ("pinned_host", "unpinned_host"):
            if kind in kinds:
                return kind
        return None

    @property
    def host_backed(self):
        return self._host_kind is not None

    # -- D2H ---------------------------------------------------------------
    def put(self, key, a):
        """Issue an async D2H of `a`; blocks only when the window is full
        (on the OLDEST in-flight transfer, double-buffer style)."""
        import jax
        if key in self._host or key in self._back:
            raise MXNetError(f"offload key {key!r} already live")
        wait_ms = 0.0
        while len(self._order) >= self.window:
            oldest = self._order.pop(0)
            t0 = time.perf_counter()
            jax.block_until_ready(self._host[oldest])
            wait_ms += (time.perf_counter() - t0) * 1e3
        self._shardings[key] = getattr(a, "sharding", None)
        if self._host_kind is not None and self._shardings[key] is not None:
            dst = self._shardings[key].with_memory_kind(self._host_kind)
            self._host[key] = jax.device_put(a, dst)
        else:
            self._host[key] = a                 # degraded: on-device ring
        self._order.append(key)
        try:
            self.d2h_bytes += int(a.nbytes)
        except (TypeError, AttributeError):
            pass
        self.puts += 1
        self.last_wait_ms = wait_ms
        self.wait_ms_total += wait_ms
        self._publish(wait_ms)
        return self._host[key]

    # -- H2D ---------------------------------------------------------------
    def prefetch(self, key):
        """Issue the async H2D back to the original sharding; returns
        immediately (call one backward-tick ahead of `get`)."""
        import jax
        if key in self._back:
            return
        if key not in self._host:
            raise MXNetError(f"offload key {key!r} not resident")
        a = self._host.pop(key)
        if key in self._order:
            self._order.remove(key)
        sh = self._shardings.pop(key)
        if self._host_kind is not None and sh is not None:
            a = jax.device_put(a, sh)
        self._back[key] = a
        try:
            self.h2d_bytes += int(a.nbytes)
        except (TypeError, AttributeError):
            pass

    def get(self, key):
        """Device-resident array for `key`; issues the H2D itself if no
        prefetch ran (then the wait is the transfer, which is the stall
        the schedule is supposed to hide)."""
        if key not in self._back:
            self.prefetch(key)
        return self._back.pop(key)

    # -- telemetry ---------------------------------------------------------
    def _publish(self, wait_ms):
        from .. import profiler
        if not profiler.is_running():
            return
        if self._counters is None:
            self._counters = (
                profiler.Counter(name="d2h_bytes"),
                profiler.Counter(name="offload_wait_ms_per_step"))
        self._counters[0].set_value(self.d2h_bytes)
        self._counters[1].set_value(round(wait_ms, 3))

    def stats(self):
        """Always-readable snapshot (counters need a running profiler)."""
        return {"puts": self.puts, "d2h_bytes": self.d2h_bytes,
                "h2d_bytes": self.h2d_bytes,
                "last_wait_ms": self.last_wait_ms,
                "wait_ms_total": self.wait_ms_total,
                "resident": len(self._host) + len(self._back),
                "in_flight": len(self._order), "window": self.window,
                "host_backed": self.host_backed}
