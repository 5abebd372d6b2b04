"""Continuous-batching decode: paged KV-cache, ragged paged attention,
DecodeScheduler, streaming /generate, chaos failover.

Acceptance criteria from the decode-serving milestone:
  * ragged paged attention (an XLA gather) matches a numpy oracle that
    walks the page indirection row by row, and the decode executable's
    trace never enters the tuner,
  * >= 64 concurrent streams through one scheduler / one ModelServer
    produce token sequences bit-identical to the sequential oracle,
    with ZERO steady-state retraces of the decode executable,
  * a saturating burst sheds with a retryable status (never hangs) and
    the KV page pool drains back to zero live pages,
  * a warm boot against a populated MXNET_EXEC_CACHE_DIR compiles
    nothing (subprocess-asserted),
  * kill -9 mid-decode leaves a flight-recorder postmortem and the
    router fails the stream over to the surviving replica,
  * TTFT / per-token histograms reach profiler.dumps() and the
    mxnet_serve_decode_* Prometheus families.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.parallel.paged_attention import paged_attention
from incubator_mxnet_tpu.serve import (DeadlineExceeded, DecodePredictor,
                                       DecodeScheduler, ModelServer,
                                       Overloaded, PageAllocator, Router)
from incubator_mxnet_tpu.serve.stats import ServingStats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 64 distinct prompts, lengths 2..6 (exercise two prefill buckets),
# every token id < the toy vocab of 32
_PROMPTS = []
for _i in range(64):
    _base = [1 + (_i % 13), 2 + (_i % 7), 3 + (_i % 5),
             4 + (_i % 11), 5 + (_i % 3), 6 + (_i % 2)]
    _PROMPTS.append(_base[: 2 + (_i % 5)])
_MAX_NEW = 5


@pytest.fixture(scope="module")
def toy():
    """One warmed DecodePredictor shared by the module (compilation is
    the slow part; token sequences do not depend on paging geometry)."""
    pred = DecodePredictor.toy(slots=4, page_size=4, num_pages=64,
                               max_pages_per_seq=8)
    warm = pred.warmup()
    return pred, warm


def _run_streams(pred, prompts, max_new=_MAX_NEW, **kw):
    """Sequential oracle: one stream at a time, full result each."""
    kw.setdefault("max_queue", len(prompts) + 8)
    sched = DecodeScheduler(pred, **kw)
    sched.start()
    try:
        return [sched.submit(p, max_new_tokens=max_new).result(timeout=120)
                for p in prompts]
    finally:
        sched.stop()


@pytest.fixture(scope="module")
def oracle(toy):
    """Expected tokens per prompt, generated one stream at a time."""
    pred, _ = toy
    return _run_streams(pred, _PROMPTS, name="decode-oracle")


# -- PageAllocator -----------------------------------------------------


def test_page_allocator_alloc_free_reuse():
    a = PageAllocator(8)
    first = a.alloc(3)
    assert first == [0, 1, 2]           # low ids first (free-list tail)
    assert (a.live, a.free_count, a.high_water) == (3, 5, 3)
    second = a.alloc(2)
    assert second == [3, 4]
    a.free(first)
    assert (a.live, a.free_count) == (2, 6)
    # freed pages come back; the pool never shrinks or moves data
    third = a.alloc(6)
    assert set(third) >= set(first)
    assert a.live == 8 and a.free_count == 0
    assert a.high_water == 8
    with pytest.raises(Overloaded, match="KV page pool exhausted"):
        a.alloc(1)
    a.free(second + third)
    assert a.live == 0 and a.free_count == 8


def test_page_allocator_errors():
    with pytest.raises(MXNetError):
        PageAllocator(0)
    a = PageAllocator(4)
    with pytest.raises(MXNetError):
        a.alloc(0)
    # all-or-nothing: a failed alloc grants no pages
    with pytest.raises(Overloaded):
        a.alloc(5)
    assert a.live == 0 and a.free_count == 4
    pages = a.alloc(2)
    a.free(pages)
    with pytest.raises(MXNetError, match="double free"):
        a.free(pages)
    # exhaustion is retryable (the 503 contract), by the shared marker
    try:
        PageAllocator(1).alloc(2)
    except Overloaded as e:
        assert e.retryable and e.status == 503


# -- paged attention: reference vs dense numpy, kernel parity ----------


def _ragged_inputs(seed=0, B=3, H=2, D=8, ps=4, P=16, max_pages=5,
                   lens=(1, 7, 20)):
    rng = np.random.RandomState(seed)
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    k_pages = rng.standard_normal((P, ps, H, D)).astype(np.float32)
    v_pages = rng.standard_normal((P, ps, H, D)).astype(np.float32)
    # distinct pages per sequence, deliberately scattered across the pool
    perm = rng.permutation(P)[: B * max_pages]
    page_table = perm.reshape(B, max_pages).astype(np.int32)
    seq_lens = np.asarray(lens, np.int32)
    return q, k_pages, v_pages, page_table, seq_lens


def _np_oracle(q, k_pages, v_pages, page_table, seq_lens):
    """Dense float64 softmax attention walking the page indirection row
    by row — the layout contract spelled out independently."""
    B, H, D = q.shape
    ps = k_pages.shape[1]
    scale = 1.0 / np.sqrt(D)
    out = np.zeros_like(q, dtype=np.float64)
    for b in range(B):
        n = max(1, int(seq_lens[b]))
        rows = [page_table[b, t // ps] * ps + t % ps for t in range(n)]
        k = k_pages.reshape(-1, H, D)[rows].astype(np.float64)
        v = v_pages.reshape(-1, H, D)[rows].astype(np.float64)
        for h in range(H):
            s = (q[b, h].astype(np.float64) * scale) @ k[:, h, :].T
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[:, h, :]
    return out.astype(np.float32)


def test_paged_attention_matches_numpy_oracle():
    args = _ragged_inputs()
    got = np.asarray(paged_attention(*args))
    want = _np_oracle(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # seq_len 0 clamps to 1 (idle-slot contract): finite, equal to len 1
    q, kp, vp, pt, sl = args
    z = np.asarray(paged_attention(q, kp, vp, pt, np.zeros_like(sl)))
    one = np.asarray(paged_attention(q, kp, vp, pt, np.ones_like(sl)))
    assert np.isfinite(z).all()
    np.testing.assert_array_equal(z, one)


def test_decode_executable_traces_without_the_tuner(toy, monkeypatch):
    """Paged attention is the XLA gather and nothing else: tracing THE
    decode executable, with Pallas candidates on offer everywhere a tuned
    site is left, moves no tuner counter and stages no kernel."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import tune
    monkeypatch.setenv("MXTPU_TUNE_INTERPRET", "1")
    pred, _ = toy
    i32 = jnp.int32
    kv = jax.ShapeDtypeStruct((pred.num_pages, pred.page_size,
                               pred.num_heads, pred.head_dim), jnp.float32)
    slots = jax.ShapeDtypeStruct((pred.slots,), i32)
    before = tune.stats()
    jaxpr = str(jax.make_jaxpr(pred._make_decode())(
        pred._param_vals, slots, slots, kv, kv,
        jax.ShapeDtypeStruct((pred.slots, pred.max_pages_per_seq), i32)))
    assert tune.stats() == before
    assert "pallas_call" not in jaxpr and "gather" in jaxpr


# -- DecodePredictor / warmup ------------------------------------------


def test_decode_warmup_reports_every_executable(toy):
    pred, warm = toy
    assert set(warm) == {"prefill:4", "prefill:8", "prefill:16", "decode"}
    assert all(kind in ("hit", "disk", "miss") for kind in warm.values())
    assert pred.is_warm
    # geometry validation is loud, not silent
    with pytest.raises(MXNetError):
        DecodePredictor.toy(slots=2, page_size=4, num_pages=4,
                            max_pages_per_seq=8)
    bad = {"emb": np.zeros((32, 16), np.float32)}
    with pytest.raises(MXNetError):
        DecodePredictor(bad, num_heads=2, head_dim=8, vocab=32)


# -- the scheduler: bit-identity + zero steady-state retraces ----------


def test_concurrent_streams_bit_identical_zero_retrace(toy, oracle):
    """64 streams submitted concurrently interleave arbitrarily across
    the 4 slots, yet every token list is bit-identical to the
    sequential oracle — and the warm decode executable never retraces."""
    pred, _ = toy
    key = pred._decode_key()
    misses_before = profiler.compile_stats().get(key, {}).get("misses", 0)
    sched = DecodeScheduler(pred, max_queue=128, name="decode-conc")
    sched.start()
    results = [None] * len(_PROMPTS)
    errors = []

    def run(i):
        try:
            st = sched.submit(_PROMPTS[i], max_new_tokens=_MAX_NEW)
            # half the clients consume token-by-token (streaming path),
            # half block on the full result
            if i % 2:
                results[i] = list(st)
            else:
                results[i] = st.result(timeout=120)
        except Exception as e:      # noqa: BLE001 — collected, asserted
            errors.append((i, e))

    try:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(_PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors[:3]
        assert results == oracle
        # iteration-level scheduling actually batched streams together
        snap = sched.stats.snapshot()
        assert snap["decode_streams_total"] == len(_PROMPTS)
        assert snap["decode_retired_total"] == len(_PROMPTS)
        assert snap["decode_tokens_total"] == sum(len(r) for r in results)
    finally:
        sched.stop()
    misses_after = profiler.compile_stats().get(key, {}).get("misses", 0)
    assert misses_after == misses_before, \
        f"decode executable retraced: {misses_before} -> {misses_after}"
    assert sched.allocator.live == 0
    assert sched.stats.snapshot()["kv_pages_live"] == 0


def test_burst_shed_and_pool_backpressure_never_hang(toy):
    """Tiny queue + tiny page pool under a thread burst: admission sheds
    retryably (never deadlocks), pool exhaustion holds the queue until
    retires free pages, and the pool drains to zero afterwards."""
    pred, _ = toy
    sched = DecodeScheduler(pred, max_queue=2, name="decode-burst")
    # 4 pages with 2-3 pages per stream: at most one stream holds pages
    # at a time, so admission backpressure is exercised for real
    sched.allocator = PageAllocator(4)
    sched.start()
    outcomes = []
    lock = threading.Lock()

    def run(i):
        try:
            toks = sched.submit(_PROMPTS[i],
                                max_new_tokens=_MAX_NEW).result(timeout=120)
            with lock:
                outcomes.append(("ok", len(toks)))
        except Overloaded as e:
            assert e.retryable
            with lock:
                outcomes.append(("shed", 0))

    try:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
            assert not t.is_alive(), "burst client hung"
        assert len(outcomes) == 24
        kinds = {k for k, _ in outcomes}
        assert "ok" in kinds         # the queue kept draining
        assert "shed" in kinds       # the bounded queue shed the burst
        assert all(n == _MAX_NEW for k, n in outcomes if k == "ok")
        assert sched.stats.snapshot()["shed_queue_full"] > 0
    finally:
        sched.stop()
    assert sched.allocator.live == 0


def test_submit_validation_and_pause_shed(toy):
    pred, _ = toy
    sched = DecodeScheduler(pred, max_queue=4, name="decode-val")
    with pytest.raises(MXNetError, match="not started"):
        sched.submit([1, 2])
    sched.start()
    try:
        with pytest.raises(MXNetError, match="empty prompt"):
            sched.submit([])
        # oversize requests are NON-retryable plain MXNetError
        with pytest.raises(MXNetError, match="exceeds the prefill ladder"):
            sched.submit(list(range(1, 20)))
        with pytest.raises(MXNetError, match="per-sequence cap"):
            sched.submit([1, 2], max_new_tokens=500)
        with pytest.raises(MXNetError, match="need >= 1"):
            sched.submit([1, 2], max_new_tokens=0)
        sched.pause("drill")
        assert not sched.accepting
        with pytest.raises(Overloaded, match="admission paused: drill"):
            sched.submit([1, 2, 3], max_new_tokens=5)
        assert sched.stats.snapshot()["shed_draining"] == 1
        sched.resume()
        assert sched.submit([1, 2, 3], max_new_tokens=5).result(timeout=60)
    finally:
        sched.stop()


def test_projected_wait_shed(toy):
    """The PR-10 admission signal: with a recorded queue-wait history,
    a 1 ms bound sheds deterministically before anything queues."""
    pred, _ = toy
    sched = DecodeScheduler(pred, max_queue=64, queue_bound_ms=1,
                            name="decode-proj")
    for _ in range(20):
        sched.stats.queue_wait.observe(0.05)    # p95 ~= 50 ms
    sched.start()
    try:
        with pytest.raises(Overloaded, match="projected queue wait"):
            sched.submit([1, 2, 3], max_new_tokens=5)
        assert sched.stats.snapshot()["shed_projected"] == 1
        assert sched.stats.snapshot()["shed_total"] >= 1
    finally:
        sched.stop()


def test_stream_cancel_frees_pages(toy):
    pred, _ = toy
    sched = DecodeScheduler(pred, max_queue=4, name="decode-cancel")
    sched.start()
    try:
        st = sched.submit([1, 2, 3], max_new_tokens=20)  # long enough
        # for the cancel to land while the stream is still in a slot
        it = iter(st)
        next(it)                    # first token landed: stream is live
        st.cancel()
        st.result(timeout=60)       # retires without error
        assert st.done and st.error is None
    finally:
        sched.stop()
    assert sched.allocator.live == 0


# -- telemetry: histograms, profiler.dumps, Prometheus -----------------


def test_decode_stats_reach_profiler_dumps(toy):
    pred, _ = toy
    profiler.set_config(profile_all=True)
    profiler.set_state("run")
    try:
        stats = ServingStats("dectest")
        sched = DecodeScheduler(pred, stats=stats, max_queue=8,
                                name="dectest")
        sched.start()
        try:
            for p in _PROMPTS[:4]:
                sched.submit(p, max_new_tokens=_MAX_NEW).result(timeout=60)
        finally:
            sched.stop()
        snap = stats.snapshot()
        assert snap["ttft_p50_ms"] > 0.0
        assert snap["token_p50_ms"] >= 0.0
        assert snap["prefill_p50_ms"] > 0.0
        assert snap["decode_step_p50_ms"] > 0.0
        assert stats.ttft.count == 4
        assert stats.token_latency.count == 4 * (_MAX_NEW - 1)
        # dumps(reset=True) surfaces the decode families exactly once
        table = profiler.dumps(reset=True)
        for needle in ("dectest:ttft_p50_ms", "dectest:token_p50_ms",
                       "dectest:decode_tokens_total",
                       "dectest:kv_page_occupancy"):
            assert needle in table, f"{needle} missing from:\n{table}"
        assert "dectest:ttft_p50_ms" not in profiler.dumps(reset=True)
    finally:
        profiler.set_state("stop")
        profiler.set_config(profile_all=False)


def test_decode_prometheus_families(toy):
    pred, _ = toy
    stats = ServingStats("promdec")
    sched = DecodeScheduler(pred, stats=stats, max_queue=8, name="promdec")
    sched.start()
    try:
        sched.submit([1, 2, 3], max_new_tokens=3).result(timeout=60)
    finally:
        sched.stop()
    text = stats.render_prometheus()
    for fam in ("mxnet_serve_decode_ttft_ms_bucket",
                "mxnet_serve_decode_ttft_ms_count",
                "mxnet_serve_decode_token_ms_bucket",
                "mxnet_serve_decode_streams_total",
                "mxnet_serve_decode_tokens_total",
                "mxnet_serve_decode_kv_pages_live"):
        assert fam in text, f"{fam} missing from:\n{text[:2000]}"
    assert 'model="promdec"' in text
    assert 'le="+Inf"' in text
    # predict-only endpoints stay exactly as before: no decode families
    assert "mxnet_serve_decode" not in ServingStats("s2").render_prometheus()


# -- ModelServer /generate ---------------------------------------------


class _NoPredict:
    """Predict-only surface stub: the decode tests never POST /predict,
    but ModelServer always builds a batcher around a predictor."""
    ladder = None
    _input_shapes = {}
    is_warm = True

    def predict(self, feed):
        raise RuntimeError("predict path unused in decode tests")


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url, json.dumps(payload).encode("utf-8"),
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _stream(url, payload, timeout=120):
    req = urllib.request.Request(
        url, json.dumps(payload).encode("utf-8"),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.headers["Content-Type"] == "application/x-ndjson"
        return [json.loads(line) for line in r if line.strip()]


def test_model_server_generate_streams_64_clients(toy, oracle):
    """The acceptance drill: 64 concurrent HTTP clients through ONE
    ModelServer, streamed ndjson chunks, every token list bit-identical
    to the sequential oracle."""
    pred, _ = toy
    sched = DecodeScheduler(pred, max_queue=128, name="decode-http")
    ms = ModelServer(_NoPredict(), decoder=sched, name="decode-http-srv")
    host, port = ms.start()
    base = f"http://{host}:{port}"
    results = [None] * len(_PROMPTS)
    errors = []

    def run(i):
        try:
            payload = {"prompt": _PROMPTS[i], "max_new_tokens": _MAX_NEW,
                       "deadline_ms": 120000}
            if i % 2:
                rows = _stream(f"{base}/generate", payload)
                assert rows[-1].get("done"), rows[-1]
                assert rows[-1]["ttft_ms"] > 0.0
                results[i] = [r["token"] for r in rows if "token" in r]
            else:
                code, body = _post(f"{base}/generate",
                                   dict(payload, stream=False), timeout=120)
                assert code == 200, body
                results[i] = body["tokens"]
        except Exception as e:      # noqa: BLE001 — collected, asserted
            errors.append((i, repr(e)))

    try:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(_PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not errors, errors[:3]
        assert results == oracle
        # the decode scheduler's stats ride the same scrape endpoints
        with urllib.request.urlopen(f"{base}/stats", timeout=30) as r:
            snap = json.loads(r.read())
        assert "decode" in snap
        assert snap["decode"]["decode_streams_total"] >= len(_PROMPTS)
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            metrics = r.read().decode("utf-8")
        assert "mxnet_serve_decode_ttft_ms_bucket" in metrics
        assert "mxnet_serve_decode_streams_total" in metrics
    finally:
        ms.stop()
    assert sched.allocator.live == 0


def test_model_server_generate_errors(toy):
    pred, _ = toy
    sched = DecodeScheduler(pred, max_queue=4, name="decode-err")
    ms = ModelServer(_NoPredict(), decoder=sched, name="decode-err-srv")
    host, port = ms.start()
    base = f"http://{host}:{port}"
    try:
        code, body = _post(f"{base}/generate", {"nope": 1})
        assert code == 400 and not body["retryable"]
        code, body = _post(f"{base}/generate",
                           {"prompt": list(range(1, 20)), "stream": False})
        assert code == 400 and not body["retryable"]
        sched.pause("drill")
        code, body = _post(f"{base}/generate",
                           {"prompt": [1, 2], "max_new_tokens": 5,
                            "stream": False})
        assert code == 503 and body["retryable"]
        sched.resume()
        # no decoder attached -> 404, not a crash
        ms2 = ModelServer(_NoPredict(), name="no-decoder")
        h2, p2 = ms2.start()
        try:
            code, body = _post(f"http://{h2}:{p2}/generate",
                               {"prompt": [1, 2]})
            assert code == 404
        finally:
            ms2.stop()
    finally:
        ms.stop()


def test_model_server_readiness_gates_on_decode_warmup():
    """/readyz stays false until the decode executables are warm — the
    router must never route a stream into a cold replica."""
    pred = DecodePredictor.toy(slots=2, page_size=4, num_pages=16,
                               max_pages_per_seq=4, prompt_buckets=(4,))
    sched = DecodeScheduler(pred, max_queue=4, name="decode-gate")
    ms = ModelServer(_NoPredict(), decoder=sched, name="decode-gate-srv")
    ms.start()
    try:
        ready, why = ms.readiness()
        assert not ready
        assert any("cold decode executables" in w for w in why)
        pred.warmup()
        assert ms.ready, ms.readiness()
    finally:
        ms.stop()


# -- warm boot: zero retraces via the shared disk exec cache -----------


_WARMBOOT = textwrap.dedent("""
    import json, os, sys
    repo, cache_dir = sys.argv[1:3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["MXNET_EXEC_CACHE_DIR"] = cache_dir
    sys.path.insert(0, repo)
    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.serve import DecodePredictor, DecodeScheduler

    pred = DecodePredictor.toy(slots=2, page_size=4, num_pages=16,
                               max_pages_per_seq=4, prompt_buckets=(4,))
    warm = pred.warmup()
    assert pred.is_warm
    sched = DecodeScheduler(pred, max_queue=4, name="warmboot")
    sched.start()
    toks = sched.submit([1, 2, 3], max_new_tokens=3).result(timeout=120)
    sched.stop()
    misses = {k: v["misses"] for k, v in profiler.compile_stats().items()
              if k.startswith("serve:")}
    sys.stdout.write("WARM " + json.dumps(warm) + chr(10))
    sys.stdout.write("MISSES " + json.dumps(misses) + chr(10))
    sys.stdout.write("TOKENS " + json.dumps(toks) + chr(10))
""")


def _parse_marked(stdout, marker):
    for line in stdout.splitlines():
        if line.startswith(marker + " "):
            return json.loads(line[len(marker) + 1:])
    raise AssertionError(f"{marker} line missing from:\n{stdout}")


@pytest.mark.timeout(420)
def test_warm_boot_zero_retrace_subprocess(tmp_path):
    """Cold process populates MXNET_EXEC_CACHE_DIR; a second process
    must reach readiness AND serve a stream with zero XLA compiles."""
    cache_dir = str(tmp_path / "exec-cache")
    os.makedirs(cache_dir)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "MXNET_EXEC_CACHE_DIR")}
    # XLA:CPU's thunk runtime serializes executables that reference
    # fusion-kernel symbols it does not embed, so a FRESH process fails
    # to deserialize them ("Symbols not found") and the disk tier
    # degrades to recompile. The legacy runtime emits self-contained
    # executables; pin it so this test exercises the cross-process
    # deserialize path the warm-boot contract is about.
    env["XLA_FLAGS"] = "--xla_cpu_use_thunk_runtime=false"
    runs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", _WARMBOOT, REPO, cache_dir],
            capture_output=True, text=True, timeout=300, env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        runs.append(r.stdout)
    cold_warm = _parse_marked(runs[0], "WARM")
    assert set(cold_warm) == {"prefill:4", "decode"}
    warm_warm = _parse_marked(runs[1], "WARM")
    assert "miss" not in warm_warm.values(), \
        f"warm boot recompiled: {warm_warm}"
    warm_misses = _parse_marked(runs[1], "MISSES")
    assert warm_misses and all(m == 0 for m in warm_misses.values()), \
        f"warm boot compiled: {warm_misses}"
    # and the executables loaded from disk compute the same stream
    assert _parse_marked(runs[0], "TOKENS") == \
        _parse_marked(runs[1], "TOKENS")


# -- chaos: kill -9 mid-decode, postmortem + router failover -----------


_REPLICA = textwrap.dedent("""
    import json, os, sys, time
    repo, outdir, idx = sys.argv[1:4]
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, repo)
    from incubator_mxnet_tpu.serve import (DecodePredictor, DecodeScheduler,
                                           ModelServer)

    class _NoPredict:
        ladder = None
        _input_shapes = {}
        is_warm = True
        def predict(self, feed):
            raise RuntimeError("unused")

    pred = DecodePredictor.toy(slots=4, page_size=4, num_pages=32,
                               max_pages_per_seq=8)
    pred.warmup()
    sched = DecodeScheduler(pred, max_queue=32, name="decode")
    srv = ModelServer(_NoPredict(), decoder=sched, name="chaos-decode")
    host, port = srv.start()
    assert srv.ready, srv.readiness()
    tmp = os.path.join(outdir, f"ready-{idx}.tmp")
    with open(tmp, "w") as f:
        json.dump({"pid": os.getpid(), "addr": f"{host}:{port}"}, f)
    os.replace(tmp, os.path.join(outdir, f"ready-{idx}.json"))
    stop = os.path.join(outdir, "stop")
    deadline = time.monotonic() + 240
    while not os.path.exists(stop) and time.monotonic() < deadline:
        time.sleep(0.05)
    srv.stop()
    sys.stdout.write("REPLICA_EXIT_OK" + chr(10))
""")


@pytest.mark.timeout(420)
def test_chaos_kill_midstream_failover_multiprocess(tmp_path, toy):
    """Two replica processes behind the router; one is SIGKILLed by the
    decode@3 fault site mid-stream (tokens already flushed). The dying
    replica leaves a flight-recorder postmortem, the router notes the
    cut stream as a replica failure and restarts the WHOLE stream on
    the survivor, and greedy decode makes the retried tokens identical
    to the oracle."""
    pred, _ = toy
    expected = _run_streams(pred, [[1, 2, 3]], max_new=5,
                            name="chaos-oracle")[0]
    outdir = tmp_path / "chaos"
    flight_dir = tmp_path / "flight"
    outdir.mkdir()
    flight_dir.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "MXNET_FAULT_INJECT",
                        "MXNET_FLIGHT_RECORDER")}
    env_victim = dict(env, MXNET_FAULT_INJECT="decode@3:kill",
                      MXNET_FLIGHT_RECORDER=str(flight_dir))
    procs = []
    try:
        for i, e in enumerate((env_victim, env)):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _REPLICA, REPO, str(outdir), str(i)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=e))
        info = {}
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline and len(info) < 2:
            for i in range(2):
                f = outdir / f"ready-{i}.json"
                if i not in info and f.exists():
                    info[i] = json.loads(f.read_text())
                if procs[i].poll() is not None:
                    raise AssertionError(
                        f"replica {i} died during boot:\n"
                        f"{procs[i].stderr.read()[-2000:]}")
            time.sleep(0.05)
        assert len(info) == 2, "replicas never became ready"

        router = Router(replicas=[info[0]["addr"], info[1]["addr"]],
                        retries=5, backoff_ms=50, name="chaos-decode")
        # round-robin guarantees the victim sees a stream within the
        # first two calls; its 3rd decode step then kills it mid-stream
        for _ in range(6):
            toks = router.generate([1, 2, 3], max_new_tokens=5,
                                   deadline_ms=60000)
            assert toks == expected
            if procs[0].poll() is not None:
                break
        deadline = time.monotonic() + 60
        while procs[0].poll() is None and time.monotonic() < deadline:
            time.sleep(0.1)
        assert procs[0].poll() == -9, "victim replica was not SIGKILLed"
        # ... and the fleet still serves
        assert router.generate([1, 2, 3], max_new_tokens=5,
                               deadline_ms=60000) == expected
        # the pre-mortem flight dump landed BEFORE the SIGKILL
        post = flight_dir / f"flight-{info[0]['pid']}.json"
        assert post.exists(), list(flight_dir.iterdir())
        payload = json.loads(post.read_text())
        assert payload["reason"] == "fault:decode#3"
        assert payload["pid"] == info[0]["pid"]
        assert payload["fault_stats"]["faults_injected"] == 0  # pre-mortem
        # survivor drains cleanly
        (outdir / "stop").touch()
        out, err = procs[1].communicate(timeout=120)
        assert procs[1].returncode == 0, err[-2000:]
        assert "REPLICA_EXIT_OK" in out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
