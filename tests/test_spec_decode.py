"""Speculative decoding: draft-propose / batched-verify, adaptive k,
page rollback, chaos failover.

Acceptance criteria from the speculative-decoding milestone:
  * the multi-query paged-attention read path is bit-compatible with
    the single-query form per query row and matches a numpy oracle on
    ragged lengths,
  * >= 16 concurrent ragged streams decoded speculatively are
    bit-identical to the plain continuous-decode oracle under greedy,
    with ZERO steady-state retraces of the verify executable,
  * spec admission composes with kv_import and prefix-cache hits
    without breaking bit-identity,
  * speculative page claims roll back: cancel/drain always returns the
    allocator to live == 0,
  * adaptive k degrades a bad draft toward plain decode depth while
    streams stay bit-identical (acceptance never trusts the draft),
  * a warm boot against a populated MXNET_EXEC_CACHE_DIR compiles
    nothing, verify executable included (subprocess-asserted),
  * kill -9 mid-VERIFY fails the stream over through the router with
    zero failed requests,
  * accept-rate / draft / verify histograms reach profiler.dumps() and
    the mxnet_serve_spec_* Prometheus families.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.parallel.paged_attention import (
    paged_attention, paged_attention_multiquery)
from incubator_mxnet_tpu.serve import (DecodePredictor, DecodeScheduler,
                                       PrefillEngine, Router, SpecDecoder)
from incubator_mxnet_tpu.serve.stats import ServingStats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 20 ragged prompts, lengths 2..6 (two prefill buckets), ids < vocab 32
_PROMPTS = []
for _i in range(20):
    _base = [1 + (_i % 13), 2 + (_i % 7), 3 + (_i % 5),
             4 + (_i % 11), 5 + (_i % 3), 6 + (_i % 2)]
    _PROMPTS.append(_base[: 2 + (_i % 5)])
# ragged decode lengths too: speculation depth clamps differently per slot
_MAX_NEW = [3 + (_i % 5) for _i in range(20)]


@pytest.fixture(scope="module")
def toy():
    """One warmed DecodePredictor shared by the module."""
    pred = DecodePredictor.toy(slots=4, page_size=4, num_pages=64,
                               max_pages_per_seq=8)
    pred.warmup()
    return pred


@pytest.fixture(scope="module")
def oracle(toy):
    """Plain (non-speculative) continuous decode, one stream at a time."""
    sched = DecodeScheduler(toy, max_queue=32, name="spec-oracle")
    sched.start()
    try:
        return [sched.submit(p, max_new_tokens=n).result(timeout=120)
                for p, n in zip(_PROMPTS, _MAX_NEW)]
    finally:
        sched.stop()


# -- multi-query paged attention ---------------------------------------


def _mq_inputs(seed=0, B=3, G=4, H=2, D=8, ps=4, P=16, max_pages=5):
    rng = np.random.RandomState(seed)
    q = rng.standard_normal((B, G, H, D)).astype(np.float32)
    k_pages = rng.standard_normal((P, ps, H, D)).astype(np.float32)
    v_pages = rng.standard_normal((P, ps, H, D)).astype(np.float32)
    perm = rng.permutation(P)[: B * max_pages]
    page_table = perm.reshape(B, max_pages).astype(np.int32)
    # per-query ragged windows, including the 0-clamp padding row case
    seq_lens = rng.randint(0, ps * max_pages + 1,
                           size=(B, G)).astype(np.int32)
    return q, k_pages, v_pages, page_table, seq_lens


def test_mq_reference_matches_single_query_per_row():
    """Each (b, g) query of the multi-query form must equal the
    single-query form run on that row alone — bit-identical, since
    the verify executable's equivalence proof rests on it."""
    q, kp, vp, pt, sl = _mq_inputs()
    got = np.asarray(paged_attention_multiquery(q, kp, vp, pt, sl))
    for b in range(q.shape[0]):
        for g in range(q.shape[1]):
            want = np.asarray(paged_attention(
                q[b:b + 1, g], kp, vp, pt[b:b + 1], sl[b:b + 1, g]))
            np.testing.assert_array_equal(got[b, g], want[0])


def test_mq_matches_numpy_oracle_on_ragged_lengths_with_an_idle_slot():
    """Dense float64 softmax attention walking the page indirection row by
    row, per (sequence, query): ragged lengths inside a block, and a slot
    whose every length is 0 (idle: clamped to one key, finite)."""
    q, kp, vp, pt, sl = _mq_inputs(seed=2)
    sl[1] = 0                                     # the idle slot
    sl[0] = [1, 2, 19, 20]                        # both ends of the range
    got = np.asarray(paged_attention_multiquery(q, kp, vp, pt, sl))
    B, G, H, D = q.shape
    ps = kp.shape[1]
    want = np.zeros(q.shape, np.float64)
    for b in range(B):
        for g in range(G):
            n = max(1, int(sl[b, g]))
            rows = [pt[b, t // ps] * ps + t % ps for t in range(n)]
            k = kp.reshape(-1, H, D)[rows].astype(np.float64)
            v = vp.reshape(-1, H, D)[rows].astype(np.float64)
            for h in range(H):
                s = (q[b, g, h].astype(np.float64) / np.sqrt(D)) @ k[:, h].T
                p = np.exp(s - s.max())
                want[b, g, h] = (p / p.sum()) @ v[:, h]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # an idle slot reads exactly what a length of 1 reads
    one = sl.copy()
    one[1] = 1
    np.testing.assert_array_equal(
        got, np.asarray(paged_attention_multiquery(q, kp, vp, pt, one)))


def test_verify_executable_traces_without_the_tuner(toy, monkeypatch):
    """As the decode executable: THE verify executable's trace moves no
    tuner counter and stages no kernel, candidates on offer or not."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import tune
    monkeypatch.setenv("MXTPU_TUNE_INTERPRET", "1")
    spec = SpecDecoder(toy, k=3)
    i32 = jnp.int32
    kv = jax.ShapeDtypeStruct((toy.num_pages, toy.page_size, toy.num_heads,
                               toy.head_dim), jnp.float32)
    sg = jax.ShapeDtypeStruct((toy.slots, spec.width), i32)
    before = tune.stats()
    jaxpr = str(jax.make_jaxpr(spec._make_verify())(
        toy._param_vals, sg, sg, kv, kv,
        jax.ShapeDtypeStruct((toy.slots, toy.max_pages_per_seq), i32)))
    assert tune.stats() == before
    assert "pallas_call" not in jaxpr and "gather" in jaxpr


# -- SpecDecoder construction / warmup ---------------------------------


def test_spec_decoder_validation_and_warmup(toy):
    with pytest.raises(MXNetError, match="need >= 1"):
        SpecDecoder(toy, k=0)
    spec = SpecDecoder(toy, k=3)
    assert spec.width == 4
    assert not spec.is_warm
    warm = spec.warmup()
    assert set(warm) == {"verify"}
    assert warm["verify"] in ("hit", "disk", "miss")
    assert spec.is_warm
    key = spec._verify_key()
    assert key.startswith("serve:verify[s4,g4,")


def test_adaptive_k_policy(toy):
    spec = SpecDecoder(toy, k=4, adapt=True, accept_floor_pct=50)
    assert spec.next_k(4, None) == 4            # no evidence: hold
    assert spec.next_k(4, 0.2) == 3             # below floor: shrink
    assert spec.next_k(1, 0.0) == 1             # never below 1
    assert spec.next_k(2, 0.95) == 3            # near-full: regrow
    assert spec.next_k(4, 1.0) == 4             # capped at k
    assert spec.next_k(3, 0.7) == 3             # hysteresis band: hold
    frozen = SpecDecoder(toy, k=4, adapt=False)
    assert frozen.next_k(4, 0.0) == 4


# -- the scheduler: bit-identity + zero retraces + rollback ------------


def test_spec_streams_bit_identical_zero_retrace(toy, oracle):
    """20 ragged streams decoded speculatively (concurrent submission,
    arbitrary slot interleaving, per-stream adaptive depth) emit token
    lists bit-identical to plain decode — and the warm verify
    executable never retraces."""
    sched = DecodeScheduler(toy, max_queue=32, spec_decode=True,
                            name="spec-conc")
    sched.start()               # start() AOT-warms the verify executable
    assert sched.spec is not None and sched.spec.is_warm
    key = sched.spec._verify_key()
    misses_before = profiler.compile_stats().get(key, {}).get("misses", 0)
    results = [None] * len(_PROMPTS)
    errors = []

    def run(i):
        try:
            st = sched.submit(_PROMPTS[i], max_new_tokens=_MAX_NEW[i])
            results[i] = list(st) if i % 2 else st.result(timeout=120)
        except Exception as e:      # noqa: BLE001 — collected, asserted
            errors.append((i, repr(e)))

    try:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(_PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors[:3]
        assert results == oracle
        snap = sched.stats.snapshot()
        assert snap["spec_steps_total"] > 0
        assert snap["spec_tokens_proposed_total"] > 0
        assert snap["spec_tokens_accepted_total"] > 0
        # self-drafting replays the target's math: near-total acceptance
        assert snap["spec_accept_rate_mean"] > 0.9
        # dispatch amortization really happened: fewer verify steps than
        # emitted tokens (plain decode pays one dispatch per token)
        assert snap["spec_steps_total"] < snap["decode_tokens_total"]
    finally:
        sched.stop()
    misses_after = profiler.compile_stats().get(key, {}).get("misses", 0)
    assert misses_after == misses_before, \
        f"verify executable retraced: {misses_before} -> {misses_after}"
    assert sched.allocator.live == 0


def test_spec_kv_import_admission_bit_identical(toy, oracle):
    """Disaggregated admission under speculation: a stream admitted from
    shipped KV rows continues speculatively and stays bit-identical."""
    eng = PrefillEngine(toy, chunk=8, name="spec-imp-eng")
    eng.warmup()
    sched = DecodeScheduler(toy, max_queue=8, spec_decode=True,
                            name="spec-import")
    sched.start()
    try:
        for i in (0, 3, 7):
            out = eng.run(_PROMPTS[i])
            imp = {"k_rows": out["k_rows"], "v_rows": out["v_rows"],
                   "n": out["n"], "next_token": out["next_token"]}
            got = sched.submit(_PROMPTS[i], max_new_tokens=_MAX_NEW[i],
                               kv_import=imp).result(timeout=60)
            assert got == oracle[i]
    finally:
        sched.stop()
    assert sched.allocator.live == 0
    if eng.prefix_cache is not None:
        eng.prefix_cache.clear()
    assert eng.allocator.live == 0


def test_spec_prefix_cache_admission_bit_identical(toy, oracle):
    """Prefix-cache hits under speculation: the CoW-forked tail page is
    the stream's own, so speculative writes never touch shared pages and
    cached re-admissions stay bit-identical."""
    eng = PrefillEngine(toy, chunk=8, prefix_cache=True,
                        name="spec-cache-eng")
    eng.warmup()
    sched = DecodeScheduler(toy, max_queue=8, spec_decode=True,
                            prefix_cache=True, chunk_prefill=eng.chunker,
                            name="spec-cache")
    sched.start()
    try:
        i = 4                       # length-6 prompt: cacheable prefix
        first = sched.submit(_PROMPTS[i],
                             max_new_tokens=_MAX_NEW[i]).result(timeout=60)
        second = sched.submit(_PROMPTS[i],
                              max_new_tokens=_MAX_NEW[i]).result(timeout=60)
        assert first == oracle[i] and second == oracle[i]
        assert sched.prefix_cache.stats()["hits"] >= 1
    finally:
        sched.stop()
    # after drain the cache's holds are the only live refcounts; clearing
    # them must reach exactly zero — speculation leaked no page
    assert sched.allocator.live == sched.prefix_cache.stats()["cached_pages"]
    sched.prefix_cache.clear()
    assert sched.allocator.live == 0
    assert sched.allocator.free_count == toy.num_pages


def test_spec_cancel_and_drain_roll_back_pages(toy):
    """Rejection rollback is position-only, so cancel mid-speculation
    and a draining stop both return the pool to zero live pages."""
    sched = DecodeScheduler(toy, max_queue=8, spec_decode=True,
                            name="spec-cancel")
    sched.start()
    try:
        st = sched.submit([1, 2, 3], max_new_tokens=24)
        it = iter(st)
        next(it)                    # stream is live in a slot
        st.cancel()
        st.result(timeout=60)
        assert st.done and st.error is None
        # a second wave left running when stop() drains
        running = [sched.submit(p, max_new_tokens=8) for p in _PROMPTS[:4]]
    finally:
        sched.stop()
    for st in running:
        assert st.done
    assert sched.allocator.live == 0
    assert sched.stats.snapshot()["kv_pages_live"] == 0


class _BadDraft:
    """Deliberately useless draft: always proposes token 0. Acceptance
    must reject nearly everything, adaptive k must walk down to 1, and
    the emitted stream must STILL be bit-identical (only verified
    tokens are ever emitted)."""

    def propose(self, last_token, k):
        return [0] * int(k)

    def sync(self, base, written):
        pass


def test_spec_adaptive_k_shrinks_on_bad_draft(toy, oracle):
    sched = DecodeScheduler(toy, max_queue=8, spec_decode=True,
                            name="spec-bad-draft")
    sched.spec._draft_factory = lambda prompt: _BadDraft()
    sched.start()
    try:
        i = 3                       # max_new 6: enough steps to walk down
        got = sched.submit(_PROMPTS[i],
                           max_new_tokens=_MAX_NEW[i]).result(timeout=60)
        assert got == oracle[i]
        snap = sched.stats.snapshot()
        assert snap["spec_accept_rate_mean"] < 0.5
        # the per-stream depth shrank below the configured cap
        assert 1.0 <= snap["spec_adaptive_k"] < sched.spec.k
    finally:
        sched.stop()
    assert sched.allocator.live == 0


# -- telemetry: profiler.dumps + Prometheus ----------------------------


def test_spec_stats_reach_profiler_dumps(toy):
    profiler.set_config(profile_all=True)
    profiler.set_state("run")
    try:
        stats = ServingStats("spectest")
        sched = DecodeScheduler(toy, stats=stats, max_queue=8,
                                spec_decode=True, name="spectest")
        sched.start()
        try:
            for p in _PROMPTS[:4]:
                sched.submit(p, max_new_tokens=5).result(timeout=60)
        finally:
            sched.stop()
        snap = stats.snapshot()
        assert snap["spec_steps_total"] > 0
        assert snap["spec_verify_p50_ms"] > 0.0
        assert 0.0 <= snap["spec_accept_rate_mean"] <= 1.0
        table = profiler.dumps(reset=True)
        for needle in ("spectest:spec_steps_total",
                       "spectest:spec_accept_rate_mean",
                       "spectest:spec_verify_p50_ms",
                       "spectest:spec_adaptive_k"):
            assert needle in table, f"{needle} missing from:\n{table}"
        # dumps(reset=True) is consistent: families surface exactly once
        assert "spectest:spec_steps_total" not in profiler.dumps(reset=True)
    finally:
        profiler.set_state("stop")
        profiler.set_config(profile_all=False)


def test_spec_prometheus_families(toy):
    stats = ServingStats("promspec")
    sched = DecodeScheduler(toy, stats=stats, max_queue=8,
                            spec_decode=True, name="promspec")
    sched.start()
    try:
        sched.submit([1, 2, 3], max_new_tokens=4).result(timeout=60)
    finally:
        sched.stop()
    text = stats.render_prometheus()
    for fam in ("mxnet_serve_spec_accept_rate_bucket",
                "mxnet_serve_spec_accept_rate_count",
                "mxnet_serve_spec_draft_ms_bucket",
                "mxnet_serve_spec_verify_ms_bucket",
                "mxnet_serve_spec_steps_total",
                "mxnet_serve_spec_tokens_proposed_total",
                "mxnet_serve_spec_tokens_accepted_total",
                "mxnet_serve_spec_adaptive_k"):
        assert fam in text, f"{fam} missing from:\n{text[:2000]}"
    assert 'model="promspec"' in text
    # non-speculative decode emits NO spec families (gated on steps)
    plain = ServingStats("promplain")
    psched = DecodeScheduler(toy, stats=plain, max_queue=8,
                             name="promplain")
    psched.start()
    try:
        psched.submit([1, 2, 3], max_new_tokens=3).result(timeout=60)
    finally:
        psched.stop()
    assert "mxnet_serve_spec" not in plain.render_prometheus()


# -- router: SLO-split placement + per-attempt token accounting --------


def _slo_router(**kw):
    kw.setdefault("slo_split", True)
    return Router(replicas=["seed:0"], ttft_slo_ms=500, token_slo_ms=100,
                  name="slo-test", **kw)


def _load_table(router, rows):
    router.set_replicas([f"{rid}:1" for rid in rows])
    with router._rlock:
        for i, (rid, (role, load)) in enumerate(rows.items()):
            info = router._replicas[f"static{i}"]
            info["addr"] = f"{rid}:1"
            info["role"] = role
            info["load"] = load


def test_router_slo_split_decode_ranking():
    """Decode candidates rank by inter-token-SLO headroom (100 ms SLO):
    proven-fast first, no-evidence neutral middle, SLO-violating last —
    kv_pages_free only breaks headroom ties."""
    r = _slo_router()
    _load_table(r, {
        "fast": ("decode", {"token_p99_ms": 20.0, "kv_pages_free": 4}),
        "slow": ("decode", {"token_p99_ms": 150.0, "kv_pages_free": 64}),
        "cold": ("both", {}),
    })
    addrs = [a for _, a in r._candidates(role="decode")]
    assert addrs == ["fast:1", "cold:1", "slow:1"]
    # split OFF: pure page-headroom ordering (the PR-16 policy)
    r2 = _slo_router(slo_split=False)
    _load_table(r2, {
        "fast": ("decode", {"token_p99_ms": 20.0, "kv_pages_free": 4}),
        "slow": ("decode", {"token_p99_ms": 150.0, "kv_pages_free": 64}),
        "cold": ("both", {}),
    })
    addrs = [a for _, a in r2._candidates(role="decode")]
    assert addrs[0] == "slow:1"


def test_router_slo_split_prefill_ranking():
    """Prefill candidates: dedicated tier always outranks colocated,
    then TTFT-SLO headroom (500 ms SLO) orders within the tier."""
    r = _slo_router()
    _load_table(r, {
        "busy": ("prefill", {"prefill_p99_ms": 400.0}),
        "idle": ("prefill", {"prefill_p99_ms": 100.0}),
        "colo": ("both", {"ttft_p99_ms": 50.0}),
    })
    addrs = [a for _, a in r._candidates(role="prefill")]
    # colo has the MOST headroom but is not dedicated: still last
    assert addrs == ["idle:1", "busy:1", "colo:1"]
    assert r._ttft_headroom({"prefill_p99_ms": 400.0}) == 100.0
    assert r._ttft_headroom({"ttft_p99_ms": 50.0}) == 450.0
    assert r._ttft_headroom({}) == 0.0
    assert r._token_headroom({"token_p99_ms": 30.0}) == 70.0


# -- warm boot: the verify executable rides the disk exec cache --------


_WARMBOOT = textwrap.dedent("""
    import json, os, sys
    repo, cache_dir = sys.argv[1:3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["MXNET_EXEC_CACHE_DIR"] = cache_dir
    os.environ["MXNET_SPEC_DECODE"] = "1"
    sys.path.insert(0, repo)
    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.serve import DecodePredictor, DecodeScheduler

    pred = DecodePredictor.toy(slots=2, page_size=4, num_pages=16,
                               max_pages_per_seq=4, prompt_buckets=(4,))
    warm = pred.warmup()
    sched = DecodeScheduler(pred, max_queue=4, name="specwarmboot")
    warm.update(sched.spec.warmup())
    sched.start()
    toks = sched.submit([1, 2, 3], max_new_tokens=4).result(timeout=120)
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
def test_spec_warm_boot_zero_retrace_subprocess(tmp_path):
    """Cold process populates MXNET_EXEC_CACHE_DIR (verify executable
    included); a second process must serve a speculative stream with
    zero XLA compiles and the identical token list."""
    cache_dir = str(tmp_path / "exec-cache")
    os.makedirs(cache_dir)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "MXNET_EXEC_CACHE_DIR",
                        "MXNET_SPEC_DECODE")}
    # legacy CPU runtime: self-contained serialized executables (the
    # thunk runtime drops fusion symbols and degrades disk to recompile)
    env["XLA_FLAGS"] = "--xla_cpu_use_thunk_runtime=false"
    runs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", _WARMBOOT, REPO, cache_dir],
            capture_output=True, text=True, timeout=300, env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        runs.append(r.stdout)
    cold_warm = _parse_marked(runs[0], "WARM")
    assert set(cold_warm) == {"prefill:4", "decode", "verify"}
    warm_warm = _parse_marked(runs[1], "WARM")
    assert "miss" not in warm_warm.values(), \
        f"warm boot recompiled: {warm_warm}"
    warm_misses = _parse_marked(runs[1], "MISSES")
    assert warm_misses and all(m == 0 for m in warm_misses.values()), \
        f"warm boot compiled: {warm_misses}"
    assert any(k.startswith("serve:verify[") for k in warm_misses), \
        f"verify executable missing from compile stats: {warm_misses}"
    assert _parse_marked(runs[0], "TOKENS") == \
        _parse_marked(runs[1], "TOKENS")


# -- chaos: kill -9 mid-VERIFY, router failover, zero failed requests --


_REPLICA = textwrap.dedent("""
    import json, os, sys, time
    repo, outdir, idx = sys.argv[1:4]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["MXNET_SPEC_DECODE"] = "1"
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
    srv = ModelServer(_NoPredict(), decoder=sched, name="chaos-spec")
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
def test_spec_chaos_kill_mid_verify_failover_multiprocess(tmp_path, toy,
                                                          oracle):
    """Two speculative replicas behind the router; the verify@3 fault
    site SIGKILLs one immediately before its 3rd verify dispatch,
    mid-stream. The router restarts the whole stream on the survivor
    and every request still returns the oracle tokens — zero failed
    requests."""
    expected = oracle[0]
    outdir = tmp_path / "chaos"
    flight_dir = tmp_path / "flight"
    outdir.mkdir()
    flight_dir.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "MXNET_FAULT_INJECT",
                        "MXNET_FLIGHT_RECORDER", "MXNET_SPEC_DECODE")}
    env_victim = dict(env, MXNET_FAULT_INJECT="verify@3:kill",
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
                        retries=5, backoff_ms=50, name="chaos-spec")
        ok_calls = 0
        for _ in range(6):
            toks = router.generate(_PROMPTS[0],
                                   max_new_tokens=_MAX_NEW[0],
                                   deadline_ms=60000)
            assert toks == expected
            ok_calls += 1
            if procs[0].poll() is not None:
                break
        deadline = time.monotonic() + 60
        while procs[0].poll() is None and time.monotonic() < deadline:
            time.sleep(0.1)
        assert procs[0].poll() == -9, "victim replica was not SIGKILLed"
        toks = router.generate(_PROMPTS[0], max_new_tokens=_MAX_NEW[0],
                               deadline_ms=60000)
        assert toks == expected
        ok_calls += 1
        # the pre-mortem flight dump names the VERIFY fault site
        post = flight_dir / f"flight-{info[0]['pid']}.json"
        assert post.exists(), list(flight_dir.iterdir())
        payload = json.loads(post.read_text())
        assert payload["reason"] == "fault:verify#3"
        # replayed partial tokens were folded into the discard counter,
        # never double-counted into the delivered tally
        snap = router.stats.snapshot()["counters"]
        assert snap["stream_tokens_total"] == ok_calls * len(expected)
        assert snap.get("stream_tokens_discarded_total", 0) >= 1
        # survivor drains cleanly
        (outdir / "stop").touch()
        out, err = procs[1].communicate(timeout=120)
        assert procs[1].returncode == 0, err[-2000:]
        assert "REPLICA_EXIT_OK" in out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
