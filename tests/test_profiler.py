"""Profiler core + runtime telemetry (memory, compile tracker, /metrics).

Covers the observability milestone:
  * Counter increment/decrement is atomic under thread contention,
  * dumps(format="json") is strict JSON (no bare Infinity/NaN),
  * Domain/Task categories and Marker instant scopes land in the trace,
  * dump() output round-trips tools/validate_trace.py (X/i/C phases),
  * pause/resume suppression, is_running gating, dumps(reset=True),
  * the compile table shows cache hits after a steady-state fused-Adam
    loop and a deliberate shape change increments recompiles_per_step,
  * profile_memory accounts per-device live/peak bytes within 10% of
    test-side accounting and emits live-bytes counter tracks,
  * GET /metrics serves valid Prometheus text exposition with serving
    and trainer counters.
"""
import gc
import json
import os
import re
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd, profiler

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
from validate_trace import TraceFormatError, validate_trace  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_profiler():
    """Every test starts and ends with a stopped, empty profiler."""
    profiler.stop()
    profiler.dumps(reset=True)
    yield
    profiler.stop()
    profiler.set_config()        # restore defaults (filename, memory off)
    profiler.dumps(reset=True)


# ---------------------------------------------------------------------------
# Counter atomicity (the increment read-modify-write race)
# ---------------------------------------------------------------------------

def test_counter_increment_is_atomic_across_threads():
    c = profiler.Counter(name="race")
    n_threads, n_incr = 8, 1000
    start = threading.Barrier(n_threads)

    def bump():
        start.wait()
        for _ in range(n_incr):
            c.increment(1)

    threads = [threading.Thread(target=bump) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c._value == n_threads * n_incr
    j = json.loads(profiler.dumps(format="json"))
    assert j["counters"]["race"]["value"] == n_threads * n_incr
    assert j["counters"]["race"]["samples"] == n_threads * n_incr
    c.decrement(8000)
    assert c._value == 0


# ---------------------------------------------------------------------------
# strict JSON
# ---------------------------------------------------------------------------

def _loads_strict(s):
    def boom(tok):
        raise AssertionError(f"non-strict JSON token {tok!r} in output")
    return json.loads(s, parse_constant=boom)


def test_dumps_json_is_strict_with_counters_only():
    # counters but zero events used to serialize min_us as bare Infinity
    profiler.Counter(name="lonely").set_value(3)
    j = _loads_strict(profiler.dumps(format="json"))
    assert j["counters"]["lonely"] == {"samples": 1, "value": 3}
    assert j["stats"] == {}


def test_dumps_json_sanitizes_nonfinite_counter_values():
    profiler.Counter(name="inf").set_value(float("inf"))
    profiler.Counter(name="nan").set_value(float("nan"))
    j = _loads_strict(profiler.dumps(format="json"))
    assert j["counters"]["inf"]["value"] is None
    assert j["counters"]["nan"]["value"] is None
    # the table renderer also survives them
    assert "inf" in profiler.dumps()


# ---------------------------------------------------------------------------
# Domain / Task / Marker semantics
# ---------------------------------------------------------------------------

def test_domain_threads_into_category_and_marker_scope():
    profiler.start()
    dom = profiler.Domain("dataload")
    with dom.new_task(name="decode"):
        time.sleep(0.001)
    dom.new_marker("epoch_end").mark(scope="global")
    profiler.Marker(name="plain").mark(scope="process")
    profiler.Marker(name="weird").mark(scope="not-a-scope")
    profiler.stop()
    profiler.dump(finished=True)
    with open("profile.json") as f:
        trace = json.load(f)["traceEvents"]
    by_name = {e["name"]: e for e in trace}
    assert by_name["decode"]["cat"] == "dataload"
    assert by_name["decode"]["ph"] == "X"
    assert by_name["epoch_end"] == {
        **by_name["epoch_end"], "ph": "i", "s": "g", "cat": "dataload"}
    assert by_name["plain"]["s"] == "p"
    assert by_name["weird"]["s"] == "t"      # unknown scope -> thread
    os.remove("profile.json")
    # domain-scoped counters get a namespaced series
    dom.new_counter("items", value=7)
    j = json.loads(profiler.dumps(format="json"))
    assert j["counters"]["dataload::items"]["value"] == 7


# ---------------------------------------------------------------------------
# chrome-trace round trip through the schema validator
# ---------------------------------------------------------------------------

def test_dump_round_trips_schema_validator(tmp_path):
    out = tmp_path / "trace.json"
    profiler.set_config(filename=str(out), profile_memory=True)
    profiler.start()
    x = nd.ones((32, 32))
    (x * 3).sum().asnumpy()
    profiler.Marker(name="mid").mark()
    profiler.Counter(name="gauge").set_value(5)
    profiler.stop()
    path = profiler.dump()
    assert path == str(out)
    n = validate_trace(str(out))
    assert n > 0
    with open(out) as f:
        phases = {e["ph"] for e in json.load(f)["traceEvents"]}
    assert {"X", "i", "C"} <= phases


def test_validate_trace_rejects_malformed():
    with pytest.raises(TraceFormatError):
        validate_trace({"nope": []})
    with pytest.raises(TraceFormatError):
        validate_trace({"traceEvents": [{"name": "a", "ph": "Z", "ts": 0}]})
    with pytest.raises(TraceFormatError):    # X without dur
        validate_trace({"traceEvents": [{"name": "a", "ph": "X", "ts": 1}]})
    with pytest.raises(TraceFormatError):    # instant with dur
        validate_trace(
            {"traceEvents": [{"name": "a", "ph": "i", "ts": 1, "dur": 2}]})
    with pytest.raises(TraceFormatError):    # non-numeric counter value
        validate_trace(
            {"traceEvents": [{"name": "a", "ph": "C", "ts": 1,
                              "args": {"value": "high"}}]})
    assert validate_trace('{"traceEvents": []}') == 0


# ---------------------------------------------------------------------------
# pause / resume / reset
# ---------------------------------------------------------------------------

def test_is_running_and_reset_lifecycle():
    assert not profiler.is_running()
    profiler.start()
    assert profiler.is_running()
    profiler.pause()
    assert not profiler.is_running()
    nd.tanh(nd.ones((4,))).asnumpy()      # suppressed: events AND compile
    profiler.resume()
    assert profiler.is_running()
    nd.sigmoid(nd.ones((4,))).asnumpy()
    profiler.stop()
    assert not profiler.is_running()
    j = json.loads(profiler.dumps(format="json"))
    assert "sigmoid" in j["stats"] and "tanh" not in j["stats"]
    assert any(k.startswith("op:sigmoid") for k in j["compile"])
    assert not any(k.startswith("op:tanh") for k in j["compile"])
    # reset clears events, counters, and the compile table
    profiler.Counter(name="c").set_value(1)
    profiler.dumps(reset=True)
    j = json.loads(profiler.dumps(format="json"))
    assert j["stats"] == {} and j["counters"] == {} and j["compile"] == {}


# ---------------------------------------------------------------------------
# compile tracker through a real fused-Adam training loop
# ---------------------------------------------------------------------------

PSHAPE = (4, 3)


def _make_trainer(n=6, shape=PSHAPE, seed=0):
    rng = np.random.RandomState(seed)
    params = gluon.ParameterDict()
    for j in range(n):
        p = params.get(f"w{j:03d}", shape=shape, init="zeros")
        p.initialize()
        p.set_data(nd.array(rng.randn(*shape).astype(np.float32)))
    tr = gluon.Trainer(params, "adam", {"learning_rate": 0.01},
                       kvstore="tpu")
    return tr, [params[k] for k in sorted(params.keys())]


def _step(tr, plist, x):
    with autograd.record():
        loss = plist[0].data().reshape(-1)[0] * 0
        for p in plist:
            loss = loss + (p.data() * x).sum()
    loss.backward()
    tr.step(1)


def test_compile_table_hits_and_recompiles_per_step():
    x = nd.array(np.random.RandomState(3).randn(*PSHAPE).astype(np.float32))
    tr, plist = _make_trainer()
    profiler.start()
    try:
        for _ in range(3):
            _step(tr, plist, x)
    finally:
        profiler.stop()
    comp = profiler.compile_stats()
    fused = {k: v for k, v in comp.items() if k.startswith("fused:adam")}
    assert fused, f"no fused-adam cache keys tracked: {sorted(comp)}"
    # step 1 compiles, steps 2-3 reuse: the cache-hit columns are non-zero
    assert sum(v["hits"] for v in fused.values()) >= 2
    assert sum(v["misses"] for v in fused.values()) >= 1
    assert "fused:adam" in profiler.dumps()
    assert "Compile cache" in profiler.dumps()
    # steady state: the last step recompiled nothing
    assert tr._last_step_recompiles == 0
    # a deliberate shape change forces XLA retraces and is charged to the
    # step that caused it
    tr2, plist2 = _make_trainer(n=6, shape=(5, 2), seed=1)
    x2 = nd.array(np.random.RandomState(4).randn(5, 2).astype(np.float32))
    _step(tr2, plist2, x2)
    assert tr2._last_step_recompiles > 0
    # the window is a *global* miss delta between a trainer's consecutive
    # steps, so tr's first step after tr2's compiles absorbs them; the
    # next one shows the original trainer still runs hot
    _step(tr, plist, x)
    _step(tr, plist, x)
    assert tr._last_step_recompiles == 0


def test_compile_warn_threshold(caplog):
    import logging
    old = os.environ.get("MXNET_COMPILE_WARN_THRESHOLD")
    os.environ["MXNET_COMPILE_WARN_THRESHOLD"] = "3"
    try:
        with caplog.at_level(logging.WARNING):
            for i in range(5):
                profiler.compile_event("test:hotkey", cache_hit=False,
                                       compile_ms=1.0)
        assert any("test:hotkey" in r.message for r in caplog.records)
        assert sum("test:hotkey" in r.message
                   for r in caplog.records) == 1   # warn once per key
    finally:
        if old is None:
            del os.environ["MXNET_COMPILE_WARN_THRESHOLD"]
        else:
            os.environ["MXNET_COMPILE_WARN_THRESHOLD"] = old


def test_track_jit_detects_shape_retrace():
    import jax

    calls = []
    fn = profiler.track_jit("test:square", jax.jit(lambda a: a * a))
    fn(np.ones((4,), np.float32))           # compile
    fn(np.ones((4,), np.float32))           # hit
    fn(np.ones((8,), np.float32))           # retrace: new shape
    calls = profiler.compile_stats()["test:square"]
    assert calls["misses"] == 2
    assert calls["hits"] == 1
    assert calls["compile_ms"] > 0


# ---------------------------------------------------------------------------
# memory profiler
# ---------------------------------------------------------------------------

def test_memory_accounting_live_peak_and_counter_track(tmp_path):
    out = tmp_path / "mem.json"
    profiler.set_config(filename=str(out), profile_memory=True)
    profiler.start()
    try:
        arrays = [nd.array(np.zeros((256, 1024), np.float32))  # 1 MiB each
                  for _ in range(4)]
        expect = sum(4 * 256 * 1024 for _ in arrays)
        with profiler.Scope("bigalloc:"):
            arrays.append(nd.array(np.zeros((256, 1024), np.float32)))
            expect += 4 * 256 * 1024
    finally:
        profiler.stop()
    stats = profiler.memory_stats()
    peak = sum(stats["peak_bytes"].values())
    live = sum(stats["live_bytes"].values())
    # within 10% of test-side accounting (the window allocates nothing
    # else of consequence on CPU)
    assert expect <= peak <= expect * 1.1
    assert expect <= live <= expect * 1.1
    assert stats["alloc_events"] >= 5
    j = json.loads(profiler.dumps(format="json"))
    assert sum(j["memory"]["peak_bytes"].values()) == peak
    assert "Memory (device)" in profiler.dumps()
    # the chrome trace carries per-device live-bytes counter tracks and
    # scope-tagged allocation instants
    profiler.dump()
    validate_trace(str(out))
    with open(out) as f:
        trace = json.load(f)["traceEvents"]
    assert any(e["ph"] == "C" and e["name"].startswith("memory:live_bytes:")
               for e in trace)
    assert any(e["name"] == "alloc:bigalloc:" for e in trace)
    # frees bring live back down but never touch the peak
    del arrays
    gc.collect()
    stats = profiler.memory_stats()
    assert sum(stats["live_bytes"].values()) < peak * 0.5
    assert sum(stats["peak_bytes"].values()) == peak


def test_free_finalizer_is_lock_free():
    """GC can fire the buffer finalizer on a thread already inside a
    profiler critical section (allocations under _lock/_mlock can trigger
    a collection), so _note_free must acquire neither lock — it enqueues
    and the books settle at the next drain point."""
    with profiler._lock, profiler._mlock:
        profiler._note_free(0xDEAD)      # deadlocks here if it takes a lock
    assert 0xDEAD in profiler._pending_frees
    profiler._drain_frees()              # unknown key: drained as a no-op
    assert not profiler._pending_frees


def test_freed_buffer_id_reuse_does_not_mask_new_alloc():
    profiler.set_config(profile_memory=True)
    profiler.start()
    try:
        a = nd.array(np.zeros((64, 64), np.float32))
        buf = a._data
        key = id(buf)
        with profiler._mlock:
            assert key in profiler._mem["buffers"]
        before = profiler.memory_stats()
        # simulate: GC fired the finalizer, nothing drained yet, and a new
        # buffer recycled the same id(). _note_alloc must settle the queue
        # first — a stale entry would otherwise swallow the registration
        profiler._note_free(key)
        profiler._note_alloc(buf)
        with profiler._mlock:
            assert key in profiler._mem["buffers"]
        after = profiler.memory_stats()
        assert after["free_events"] == before["free_events"] + 1
        assert after["alloc_events"] == before["alloc_events"] + 1
        # net live bytes unchanged: one free settled, one alloc re-added
        assert after["live_bytes"] == before["live_bytes"]
    finally:
        profiler.stop()


def test_memory_hook_uninstalled_after_stop():
    from incubator_mxnet_tpu.ndarray import ndarray as ndmod
    profiler.set_config(profile_memory=True)
    profiler.start()
    assert ndmod.MEMORY_HOOK is not None
    assert profiler.memory_enabled()
    profiler.stop()
    assert ndmod.MEMORY_HOOK is None
    assert not profiler.memory_enabled()
    before = profiler.memory_stats()["alloc_events"]
    nd.ones((16, 16)).asnumpy()
    assert profiler.memory_stats()["alloc_events"] == before


# ---------------------------------------------------------------------------
# continuous dump
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_continuous_dump_writes_rolling_traces(tmp_path):
    out = tmp_path / "rolling.json"
    profiler.set_config(filename=str(out), continuous_dump=True,
                        dump_period=0.2)
    profiler.start()
    try:
        nd.ones((8, 8)).asnumpy()
        # rolling dumps write bounded segment files (rolling.NNNN.json),
        # not the final filename — that stays reserved for dump()
        deadline = time.time() + 5
        segments = []
        while not segments and time.time() < deadline:
            time.sleep(0.05)
            segments = sorted(tmp_path.glob("rolling.*.json"))
        assert segments, "dump thread never wrote a rolling trace segment"
        for seg in segments:
            validate_trace(str(seg))
    finally:
        profiler.stop()
    # the trimmed events were folded into the aggregate registry, so the
    # whole-run stats survive even though the raw buffers were cleared
    assert "_ones" in profiler.dumps()
    with profiler._lock:
        assert not any(e["name"].endswith("_ones") for e in profiler._events)


def test_rolling_dump_trims_buffers_and_skips_quiet_periods(tmp_path):
    out = tmp_path / "seg.json"
    profiler.set_config(filename=str(out))
    profiler.start()
    try:
        nd.ones((4, 4)).asnumpy()
        path = profiler.dump(finished=False)
        assert path is not None and ".json" in path and path != str(out)
        validate_trace(path)
        # buffers were cleared: an immediate second rolling dump is a no-op
        assert profiler.dump(finished=False) is None
    finally:
        profiler.stop()
    assert "_ones" in profiler.dumps()
    profiler.dumps(reset=True)
    assert "_ones" not in profiler.dumps()


# ---------------------------------------------------------------------------
# /metrics scrape surface
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+]?[0-9.eE+naif]+$")


def _assert_prometheus_text(text):
    assert text.endswith("\n")
    declared, histograms = set(), set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE"):
            _, _, fam, kind = line.split()
            declared.add(fam)
            if kind == "histogram":
                histograms.add(fam)
            continue
        if line.startswith("#"):
            continue
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
        # no stray samples: every metric belongs to a declared family.
        # Histogram samples are declared under the BASE name and emitted
        # with the spec's _bucket/_sum/_count suffixes.
        name = line.split("{", 1)[0].split(" ", 1)[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in histograms:
                name = name[: -len(suffix)]
                break
        assert name in declared, f"sample without HELP/TYPE family: {name}"


def test_render_prometheus_exposition_format():
    profiler.Counter(name='odd"name\\x').set_value(2)
    profiler.compile_event("op:test", cache_hit=True)
    text = profiler.render_prometheus()
    _assert_prometheus_text(text)
    assert "mxnet_profiler_running 0" in text
    assert 'mxnet_compile_cache_hits_total{key="op:test"} 1' in text
    # label escaping keeps quotes/backslashes inside the label legal
    assert 'name="odd\\"name\\\\x"' in text


def test_metrics_endpoint_serves_serving_and_trainer_counters(tmp_path):
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.serve import ModelServer, Predictor

    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    net(nd.array(np.zeros((1, 6), np.float32)))
    path = os.path.join(str(tmp_path), "model")
    net.export(path)
    predictor = Predictor.from_artifact(path, bucket_sizes=(2, 4, 8))

    profiler.start()
    try:
        tr, plist = _make_trainer(n=3)
        _step(tr, plist, nd.ones(PSHAPE))
        with ModelServer(predictor, max_latency_ms=2.0,
                         max_queue=32) as srv:
            host, port = srv.address
            url = f"http://{host}:{port}"
            x = np.random.rand(6).astype(np.float32).tolist()
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps({"inputs": {"data": x}}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.status == 200
            with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
                assert r.status == 200
                ctype = r.headers.get("Content-Type", "")
                text = r.read().decode()
    finally:
        profiler.stop()
    assert ctype.startswith("text/plain")
    assert "version=0.0.4" in ctype
    _assert_prometheus_text(text)
    assert "mxnet_profiler_running 1" in text
    assert 'mxnet_profiler_counter{name="serve:requests_total"}' in text
    assert 'name="trainer_dispatches_per_step"' in text
    assert 'name="recompiles_per_step"' in text
    assert "mxnet_compile_cache_misses_total" in text
    assert 'key="serve:exec[' in text


# ---------------------------------------------------------------------------
# Step-time attribution (StepTimeline) + compiler cost accounting
# ---------------------------------------------------------------------------

def test_attribution_off_is_zero_overhead():
    prev = profiler.attribution_enable(False)
    try:
        # off, span() hands back ONE shared no-op object: no allocation,
        # no lock, no counter — and the records counter stays exactly 0
        assert profiler.span("compute") is profiler.span("h2d")
        for _ in range(100):
            with profiler.span("compute", args={"k": 1}):
                with profiler.span("collective"):
                    pass
            profiler.observe_phase("queue_wait", 1.0)
            profiler.phase_step_end()
        assert profiler.span_records() == 0
        assert profiler.phase_stats() == {"steps": 0, "spans": 0,
                                          "phases": {}}
        assert profiler.last_step_phases() == {}
    finally:
        profiler.attribution_enable(prev)


def test_span_nesting_books_only_top_level_into_step_vector():
    prev = profiler.attribution_enable(True)
    try:
        with profiler.span("compute"):
            time.sleep(0.01)
            with profiler.span("collective"):
                time.sleep(0.002)
        profiler.observe_phase("queue_wait", 2.5)
        profiler.phase_step_end()
        st = profiler.phase_stats()
        assert st["spans"] == 3 and st["steps"] == 1
        assert st["phases"]["compute"]["count"] == 1
        assert st["phases"]["collective"]["count"] == 1
        v = profiler.last_step_phases()
        # the nested collective's ms is already inside compute's: only
        # top-level spans accumulate into the per-step vector
        assert set(v) == {"compute", "queue_wait"}
        assert v["compute"] >= 10.0
        assert v["queue_wait"] == pytest.approx(2.5)
        # the next step starts clean
        with profiler.span("optimizer"):
            pass
        profiler.phase_step_end()
        assert set(profiler.last_step_phases()) == {"optimizer"}
        assert profiler.phase_stats()["steps"] == 2
    finally:
        profiler.attribution_enable(prev)


def test_span_trace_events_nest_and_carry_linkage(tmp_path):
    path = tmp_path / "trace.json"
    prev = profiler.attribution_enable(True)
    profiler.set_config(filename=str(path))
    profiler.start()
    try:
        with profiler.span("compute"):
            with profiler.span("collective", args={"op": "push"}):
                time.sleep(0.002)
        profiler.phase_step_end()
        profiler.stop()
        profiler.dump()
        assert validate_trace(str(path)) > 0
        evs = json.loads(path.read_text())["traceEvents"]
        spans = {e["name"]: e for e in evs if e.get("cat") == "step"}
        parent = spans["phase:compute"]
        child = spans["phase:collective"]
        assert child["args"]["parent"] == parent["args"]["span_id"]
        assert child["args"]["trace"] == profiler.trace_id()
        assert child["args"]["op"] == "push"
        assert "parent" not in parent["args"]
        # attribution dumps anchor the perf_counter timebase to the wall
        # clock so tools/trace_merge.py can place this process's timeline
        anchors = [e for e in evs if e["name"] == "clock_sync"]
        assert anchors and anchors[-1]["args"]["peer"] == "self"
        for k in ("offset_us", "rtt_us", "perf_anchor_us",
                  "wall_anchor_us"):
            assert isinstance(anchors[-1]["args"][k], float)
    finally:
        profiler.attribution_enable(prev)


def test_validate_trace_rejects_malformed_spans():
    def ev(**kw):
        base = {"name": "phase:x", "ph": "X", "ts": 100, "dur": 50,
                "pid": 0, "cat": "step"}
        base.update(kw)
        return base

    # well-formed nesting (child inside parent) passes
    good = [ev(args={"span_id": 2, "parent": 1, "trace": "t"},
               ts=110, dur=10),
            ev(args={"span_id": 1, "trace": "t"})]
    assert validate_trace({"traceEvents": good}) == 2
    # a parent flushed into an earlier rolling segment is tolerated
    assert validate_trace({"traceEvents": [
        ev(args={"span_id": 2, "parent": 99, "trace": "t"})]}) == 1
    with pytest.raises(TraceFormatError):    # non-positive span id
        validate_trace({"traceEvents": [ev(args={"span_id": 0})]})
    with pytest.raises(TraceFormatError):    # duplicate id in one scope
        validate_trace({"traceEvents": [
            ev(args={"span_id": 3, "trace": "t"}),
            ev(args={"span_id": 3, "trace": "t"})]})
    with pytest.raises(TraceFormatError):    # child escapes its parent
        validate_trace({"traceEvents": [
            ev(args={"span_id": 1, "trace": "t"}),
            ev(args={"span_id": 2, "parent": 1, "trace": "t"},
               ts=140, dur=100)]})
    # same id on DIFFERENT pids is fine (merged multi-process timeline)
    assert validate_trace({"traceEvents": [
        ev(args={"span_id": 5, "trace": "a"}),
        ev(args={"span_id": 5, "trace": "b"}, pid=1)]}) == 2
    with pytest.raises(TraceFormatError):    # clock_sync without anchors
        validate_trace({"traceEvents": [
            {"name": "clock_sync", "ph": "M", "ts": 0,
             "args": {"offset_us": 1.0}}]})


def test_phase_histogram_rendered_in_prometheus():
    prev = profiler.attribution_enable(True)
    try:
        profiler.observe_phase("queue_wait", 0.5)
        profiler.observe_phase("queue_wait", 50.0)
        text = profiler.render_prometheus()
        assert ('mxnet_step_phase_ms_bucket{phase="queue_wait",le="+Inf"}'
                ' 2') in text
        assert 'mxnet_step_phase_ms_count{phase="queue_wait"} 2' in text
        assert 'mxnet_step_phase_ms_sum{phase="queue_wait"} 50.500' in text
        # histogram buckets are cumulative
        counts = [int(l.rsplit(" ", 1)[1]) for l in text.splitlines()
                  if l.startswith('mxnet_step_phase_ms_bucket')]
        assert counts == sorted(counts)
    finally:
        profiler.attribution_enable(prev)


def test_dumps_reset_clears_attribution_and_cost_families():
    from incubator_mxnet_tpu import fleetobs

    prev = profiler.attribution_enable(True)
    try:
        with profiler.span("compute"):
            pass
        profiler.phase_step_end()
        profiler.cost_event("trainstep:reset-probe", flops=1e9,
                            bytes_accessed=1e6)
        fleetobs._bump("snapshots_built", 2)
        payload = json.loads(profiler.dumps(reset=True, format="json"))
        assert payload["step_attribution"]["spans"] == 1
        assert payload["step_attribution"]["steps"] == 1
        assert payload["cost"]["trainstep:reset-probe"]["flops"] == 1e9
        assert payload["fleetobs"]["snapshots_built"] == 2
        # reset means reset: the NEXT dump starts from zero for every
        # family this dump reported
        after = json.loads(profiler.dumps(format="json"))
        assert "step_attribution" not in after and "cost" not in after
        assert "fleetobs" not in after
        assert profiler.span_records() == 0
        assert profiler.cost_stats() == {}
        assert profiler.last_step_phases() == {}
        assert profiler.mfu_stats() is None
        assert fleetobs.stats()["snapshots_built"] == 0
    finally:
        profiler.attribution_enable(prev)


def test_cost_accounting_populates_cached_jit_choke_points():
    """op:*, fused:*, kvstore:flat_pack* and trainstep:* all record
    compiler cost at their cached_jit executable acquisition
    (serve:exec[*], the fourth choke point, is asserted in test_serve.py
    where the predictor fixtures live). Odd shapes so every executable
    compiles fresh inside this test. The automatic compile-cache cost
    hook is gated on attribution, so the compiles run under the flag."""
    import jax.numpy as jnp

    from incubator_mxnet_tpu import optimizer as opt
    from incubator_mxnet_tpu.kvstore import _flat_pack_fn
    from incubator_mxnet_tpu.parallel import TrainStep

    prev = profiler.attribution_enable(True)
    try:
        rs = np.random.RandomState(5)
        mx.nd.dot(nd.array(rs.rand(23, 29).astype(np.float32)),
                  nd.array(rs.rand(29, 31).astype(np.float32)))
        ws = [nd.array(rs.randn(5, 9).astype(np.float32)) for _ in range(2)]
        gs = [nd.array(rs.randn(5, 9).astype(np.float32)) for _ in range(2)]
        upd = opt.get_updater(opt.create("sgd", learning_rate=0.1))
        upd([0, 1], gs, ws)
        _flat_pack_fn(((11,), (13,)))(jnp.ones((11,)), jnp.ones((13,)))
        net = gluon.nn.Dense(3, in_units=23)
        net.initialize()
        step = TrainStep(net, lambda o, l: jnp.mean((o - l) ** 2),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.05},
                         example_inputs=[mx.nd.ones((6, 23))])
        step(rs.rand(6, 23).astype(np.float32),
             rs.rand(6, 3).astype(np.float32))

        costs = profiler.cost_stats()
    finally:
        profiler.attribution_enable(prev)

    def rec(prefix):
        match = {k: v for k, v in costs.items() if k.startswith(prefix)}
        assert match, (prefix, sorted(costs))
        return next(iter(match.values()))

    assert rec("op:dot")["flops"] > 0
    assert rec("fused:sgd_update")["flops"] > 0
    # flat-pack is pure data movement: zero flops, real bytes
    assert rec("kvstore:flat_pack")["bytes_accessed"] > 0
    ts = rec("trainstep:sgd")
    assert ts["flops"] > 0 and ts["bytes_accessed"] > 0
    assert ts["intensity"] == pytest.approx(
        ts["flops"] / ts["bytes_accessed"])


def test_mfu_stats_derive_from_compiler_cost():
    import jax.numpy as jnp

    from incubator_mxnet_tpu.parallel import TrainStep
    prev = profiler.attribution_enable(True)
    try:
        net = gluon.nn.Dense(5, in_units=17)
        net.initialize()
        step = TrainStep(net, lambda o, l: jnp.mean((o - l) ** 2),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.05},
                         example_inputs=[mx.nd.ones((4, 17))])
        rs = np.random.RandomState(7)
        x = rs.rand(4, 17).astype(np.float32)
        y = rs.rand(4, 5).astype(np.float32)
        for _ in range(3):
            step(x, y)
            profiler.phase_step_end()
        mfu = profiler.mfu_stats()
        assert mfu is not None
        assert mfu["key"].startswith("trainstep:")
        assert mfu["flops_per_step"] > 0
        assert mfu["compute_ms_per_step"] > 0
        assert mfu["flops_per_sec"] > 0
        # CPU: no trustworthy peak -> mfu is null, never a made-up number
        assert mfu["peak_flops"] is None and mfu["mfu"] is None
        payload = json.loads(profiler.dumps(format="json"))
        assert payload["mfu"]["flops_per_step"] == mfu["flops_per_step"]
        assert "trainstep:sgd" in payload["cost"]
        table = profiler.dumps()
        assert "MFU (compiler cost / compute phase)" in table
        assert "Step breakdown (phase)" in table
        assert "Compiler cost (per executable)" in table
    finally:
        profiler.attribution_enable(prev)


def test_run_epoch_attributes_input_wait_and_closes_steps():
    import jax.numpy as jnp

    from incubator_mxnet_tpu.parallel import TrainStep
    net = gluon.nn.Dense(4, in_units=19)
    net.initialize()
    step = TrainStep(net, lambda o, l: jnp.mean((o - l) ** 2),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05},
                     example_inputs=[mx.nd.ones((8, 19))])
    rs = np.random.RandomState(13)
    batches = [(rs.randn(8, 19).astype(np.float32),
                rs.randn(8, 4).astype(np.float32)) for _ in range(4)]
    prev = profiler.attribution_enable(True)
    try:
        step.run_epoch(batches)
        st = profiler.phase_stats()
        assert st["steps"] == 4
        for phase in ("h2d", "compute"):
            assert st["phases"][phase]["count"] == 4, st["phases"]
        # one extra input_wait: the end-of-iterator probe that returns
        # the sentinel is itself a (tiny) wait on the input pipeline
        assert st["phases"]["input_wait"]["count"] in (4, 5)
        assert set(profiler.last_step_phases()) >= {"input_wait",
                                                    "compute"}
    finally:
        profiler.attribution_enable(prev)


def test_attributed_phases_explain_wall_step_time():
    """Acceptance oracle: with attribution on, the per-step phase sum
    explains the measured wall step time within 15% on CPU — the compute
    span syncs on the result, so attributed time is real wall time."""
    import jax.numpy as jnp

    from incubator_mxnet_tpu.parallel import TrainStep
    net = gluon.nn.Dense(256, in_units=512)
    net.initialize()
    step = TrainStep(net, lambda o, l: jnp.mean((o - l) ** 2),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05},
                     example_inputs=[mx.nd.ones((128, 512))])
    rs = np.random.RandomState(11)
    x = rs.rand(128, 512).astype(np.float32)
    y = rs.rand(128, 256).astype(np.float32)
    step(x, y)                       # compile outside the timed window
    prev = profiler.attribution_enable(True)
    try:
        profiler.dumps(reset=True)
        t0 = time.perf_counter()
        for _ in range(6):
            step(x, y)
            profiler.phase_step_end()
        wall_ms = (time.perf_counter() - t0) * 1e3
        st = profiler.phase_stats()
        assert st["steps"] == 6
        phase_ms = sum(r["total_ms"] for r in st["phases"].values())
        assert phase_ms == pytest.approx(wall_ms, rel=0.15), \
            (phase_ms, wall_ms, st["phases"])
        # compute dominates a CPU train step
        assert st["phases"]["compute"]["total_ms"] > 0.5 * phase_ms
    finally:
        profiler.attribution_enable(prev)
