"""Two-tier executable cache (compile_cache): AOT compile + persist.

Acceptance criteria from the cold-start milestone:
  * memory-tier hit/miss counters and LRU eviction behave,
  * a simulated fresh process (clear(memory=True)) deserializes from the
    disk tier instead of re-tracing (disk_hits in the compile table),
  * truncated/garbage disk entries, jax-version skew, and backend skew
    all degrade to a plain recompile with the right counters — never a
    crash, never a stale executable,
  * two processes racing a write to the same key publish atomically
    (last-writer-wins, the surviving file is valid),
  * a second Predictor boot against a warm dir records ZERO XLA retraces
    across all four track_jit choke points (op fwd/vjp, fused optimizer,
    kvstore flat-pack, serve executables),
  * exec_cache_* telemetry surfaces in dumps() and render_prometheus().
"""
import hashlib
import json
import os
import pickle
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, compile_cache as cc, gluon, nd, profiler
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.serve import Predictor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IN_DIM, OUT_DIM = 6, 4


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """Point the disk tier at a fresh directory and zero the counters.

    The global cache is shared with every other wrapper in the process
    (op registry traffic from other tests), so tests assert on per-key
    compile-table rows and counter deltas, never on absolute totals.
    """
    d = tmp_path / "exec_cache"
    monkeypatch.setenv("MXNET_EXEC_CACHE_DIR", str(d))
    cc.clear(memory=True, stats=True)
    yield str(d)
    cc.clear(memory=True, stats=True)


def _misses(key):
    return profiler.compile_stats().get(key, {}).get("misses", 0)


def _disk_hits(key):
    return profiler.compile_stats().get(key, {}).get("disk_hits", 0)


# ---------------------------------------------------------------------------
# memory tier
# ---------------------------------------------------------------------------

def test_memory_hit_miss_and_per_key_table(cache_dir):
    f = cc.cached_jit("test:mem", lambda a: a * 2.0)
    x = np.ones((4,), np.float32)
    before = cc.stats()
    m0, h0 = _misses("test:mem"), 0
    np.testing.assert_allclose(np.asarray(f(x)), 2 * x)
    np.testing.assert_allclose(np.asarray(f(x)), 2 * x)
    f(np.ones((8,), np.float32))            # new shape: second executable
    after = cc.stats()
    assert after["misses"] - before["misses"] == 2
    assert after["hits"] - before["hits"] == 1
    assert after["mem_entries"] >= 2
    row = profiler.compile_stats()["test:mem"]
    assert row["misses"] - m0 == 2 and row["hits"] >= 1
    # disk tier captured both executables
    assert cc.disk_stats()["entries"] == 2
    assert cc.disk_stats()["bytes"] > 0


def test_lru_eviction_under_small_cap(cache_dir, monkeypatch):
    monkeypatch.setenv("MXNET_EXEC_CACHE_SIZE", "2")
    f = cc.cached_jit("test:lru", lambda a: a + 1.0)
    before = cc.stats()
    for n in (2, 3, 4, 5):                  # 4 signatures through a 2-slot LRU
        x = np.ones((n,), np.float32)
        np.testing.assert_allclose(np.asarray(f(x)), x + 1)
    after = cc.stats()
    assert after["evictions"] - before["evictions"] >= 2
    assert after["mem_entries"] <= 2
    # evicted signatures still answer correctly (disk tier backfills)
    x = np.ones((2,), np.float32)
    np.testing.assert_allclose(np.asarray(f(x)), x + 1)
    assert cc.stats()["misses"] == after["misses"]   # no recompile


# ---------------------------------------------------------------------------
# disk tier: fresh-process roundtrip
# ---------------------------------------------------------------------------

def test_disk_roundtrip_simulated_cold_boot(cache_dir):
    f = cc.cached_jit("test:roundtrip", lambda a, b: a @ b)
    x = np.eye(4, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(f(x, x)), x)
    m_before, d_before = _misses("test:roundtrip"), _disk_hits("test:roundtrip")
    s_before = cc.stats()
    cc.clear(memory=True)                   # what a fresh replica sees
    np.testing.assert_allclose(np.asarray(f(x, x)), x)
    s_after = cc.stats()
    assert s_after["disk_hits"] - s_before["disk_hits"] == 1
    assert s_after["misses"] == s_before["misses"]
    # the compile table distinguishes a deserialize-hit from a retrace
    assert _disk_hits("test:roundtrip") - d_before == 1
    assert _misses("test:roundtrip") == m_before
    # and from a plain memory hit
    np.testing.assert_allclose(np.asarray(f(x, x)), x)
    assert _disk_hits("test:roundtrip") - d_before == 1


def test_warmup_from_shape_structs(cache_dir):
    import jax
    f = cc.cached_jit("test:warmup", lambda a: a.sum())
    aval = jax.ShapeDtypeStruct((16,), np.float32)
    assert f.warmup(aval) == "miss"
    assert f.warmup(aval) == "hit"
    cc.clear(memory=True)
    assert f.warmup(aval) == "disk"
    # the AOT-warmed executable serves a real array without a retrace
    before = cc.stats()["misses"]
    out = f(np.ones((16,), np.float32))
    assert float(np.asarray(out)) == 16.0
    assert cc.stats()["misses"] == before


# ---------------------------------------------------------------------------
# robustness: corruption and fingerprint skew degrade to recompile
# ---------------------------------------------------------------------------

def _entries(cache_dir):
    return sorted(p for p in os.listdir(cache_dir) if p.endswith(".mxec"))


@pytest.mark.parametrize("corrupt", ["truncate", "garbage"])
def test_corrupt_disk_entry_falls_back_to_recompile(cache_dir, corrupt):
    f = cc.cached_jit(f"test:corrupt_{corrupt}", lambda a: a - 3.0)
    x = np.full((5,), 7.0, np.float32)
    np.testing.assert_allclose(np.asarray(f(x)), x - 3)
    (name,) = _entries(cache_dir)
    path = os.path.join(cache_dir, name)
    if corrupt == "truncate":
        with open(path, "r+b") as fh:
            fh.truncate(32)                 # magic survives, fp/sha do not
    else:
        with open(path, "wb") as fh:
            fh.write(b"\x00not an mxec entry\xff" * 16)
    before = cc.stats()
    cc.clear(memory=True)
    np.testing.assert_allclose(np.asarray(f(x)), x - 3)   # recompiled fine
    after = cc.stats()
    assert after["disk_errors"] - before["disk_errors"] == 1
    assert after["misses"] - before["misses"] == 1
    assert after["disk_hits"] == before["disk_hits"]
    # the bad entry was deleted and the recompile republished a good one
    assert _entries(cache_dir) == [name]
    cc.clear(memory=True)
    np.testing.assert_allclose(np.asarray(f(x)), x - 3)
    assert cc.stats()["disk_hits"] - after["disk_hits"] == 1


@pytest.mark.parametrize("field", ["_jax_version", "_backend"])
def test_version_and_backend_skew_miss_instead_of_stale(cache_dir, field):
    def build():
        return cc.cached_jit(f"test:skew_{field}", lambda a: a * 5.0)

    x = np.ones((3,), np.float32)
    np.testing.assert_allclose(np.asarray(build()(x)), x * 5)
    assert len(_entries(cache_dir)) == 1
    # a process on a different jax version / backend computes a different
    # fingerprint for the same call: the stored executable MUST NOT load
    orig = getattr(cc, field)
    setattr(cc, field, lambda: "skewed-elsewhere")
    try:
        before = cc.stats()
        cc.clear(memory=True)
        np.testing.assert_allclose(np.asarray(build()(x)), x * 5)
        after = cc.stats()
        assert after["misses"] - before["misses"] == 1
        assert after["disk_hits"] == before["disk_hits"]
        assert len(_entries(cache_dir)) == 2    # both worlds keep theirs
    finally:
        setattr(cc, field, orig)
    cc.clear(memory=True)
    np.testing.assert_allclose(np.asarray(build()(x)), x * 5)
    assert cc.stats()["disk_hits"] - after["disk_hits"] == 1


def test_disk_budget_evicts_oldest(cache_dir, monkeypatch):
    f = cc.cached_jit("test:budget_probe", lambda a: a + 0.5)
    f(np.ones((2,), np.float32))
    (probe,) = _entries(cache_dir)
    size = os.stat(os.path.join(cache_dir, probe)).st_size
    monkeypatch.setenv("MXNET_EXEC_CACHE_DISK_BYTES", str(int(size * 2.5)))
    before = cc.stats()
    g = cc.cached_jit("test:budget_fill", lambda a: a * 0.5)
    for n in (3, 4, 5):
        g(np.ones((n,), np.float32))
    after = cc.stats()
    assert after["evictions"] - before["evictions"] >= 1
    assert after["bytes"] <= int(size * 2.5)
    assert len(_entries(cache_dir)) < 4
    # unbounded budget stops evicting
    monkeypatch.setenv("MXNET_EXEC_CACHE_DISK_BYTES", "0")
    g(np.ones((6,), np.float32))
    assert cc.stats()["evictions"] == after["evictions"]


# ---------------------------------------------------------------------------
# concurrency: two processes race a write to the same key
# ---------------------------------------------------------------------------

_RACE_SCRIPT = """
import os, sys
import numpy as np
sys.path.insert(0, {repo!r})
from incubator_mxnet_tpu import compile_cache as cc
f = cc.cached_jit("test:twoproc", lambda a, b: a @ b + 1.0)
x = np.ones((8, 8), np.float32)
r = f(x, x)
assert float(np.asarray(r)[0, 0]) == 9.0
print("entries", *sorted(p for p in os.listdir(os.environ["MXNET_EXEC_CACHE_DIR"])
                         if p.endswith(".mxec")))
"""


def test_concurrent_two_process_write_last_writer_wins(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_EXEC_CACHE_DIR=cache_dir)
    script = _RACE_SCRIPT.format(repo=REPO)
    procs = [subprocess.Popen([sys.executable, "-c", script], env=env,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True)
             for _ in range(2)]
    outs = [p.communicate(timeout=300) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"racer failed:\n{out}\n{err}"
    # both racers computed the same fingerprint; exactly one file survived
    # the pair of atomic renames and no tmp droppings remain
    names = os.listdir(cache_dir)
    assert len([n for n in names if n.endswith(".mxec")]) == 1
    assert not [n for n in names if ".tmp." in n]
    assert outs[0][0] == outs[1][0]
    # the survivor is a complete, checksum-valid entry...
    (name,) = _entries(cache_dir)
    with open(os.path.join(cache_dir, name), "rb") as fh:
        raw = fh.read()
    assert raw.startswith(b"MXEC1\n")
    assert raw[6:70].decode() == name[:-len(".mxec")]
    body = raw[136:]
    assert hashlib.sha256(body).hexdigest() == raw[71:135].decode()
    payload, in_tree, out_tree, device_ids = pickle.loads(body)
    assert device_ids == [0]
    assert payload
    # ...that a third, fresh process deserializes instead of recompiling
    third = subprocess.run(
        [sys.executable, "-c", script + "\nassert cc.stats()['disk_hits'] == 1"
         "\nassert cc.stats()['misses'] == 0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert third.returncode == 0, third.stderr


_JAX_CACHED_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
import jax
from incubator_mxnet_tpu import compile_cache as cc
from incubator_mxnet_tpu.serve import DecodePredictor, DecodeScheduler
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
pred = DecodePredictor.toy(slots=2, page_size=4, num_pages=16,
                           max_pages_per_seq=4, prompt_buckets=(4,))
warm = pred.warmup()
sched = DecodeScheduler(pred, max_queue=4, name="jaxcached")
sched.start()
try:
    toks = sched.submit([1, 2, 3], max_new_tokens=3).result(timeout=60)
finally:
    sched.stop()
print("SAID " + json.dumps([warm, toks]))
"""


@pytest.mark.timeout(420)
def test_the_tier_writes_no_executable_of_a_program_jax_s_cache_loaded(
        tmp_path):
    """A first process fills jax's own persistent cache alone; a second,
    with the disk tier on, finds the same programs there and writes the
    tier's entries; a third loads those and serves a stream. On XLA:CPU a
    process that loaded a program from jax's cache serializes it without
    its kernels, so the tier compiles what it writes with jax's cache left
    out, and the third process computes what the first did."""
    jax_dir, tier = str(tmp_path / "jax"), str(tmp_path / "tier")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "MXNET_EXEC_CACHE_DIR")}
    # the legacy runtime, as the warm-boot tests of tests/test_decode.py
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=jax_dir,
               XLA_FLAGS="--xla_cpu_use_thunk_runtime=false")
    script = _JAX_CACHED_SCRIPT.format(repo=REPO)
    said = []
    for extra in ({}, {"MXNET_EXEC_CACHE_DIR": tier},
                  {"MXNET_EXEC_CACHE_DIR": tier}):
        r = subprocess.run([sys.executable, "-c", script],
                           env=dict(env, **extra), capture_output=True,
                           text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        said.append(json.loads(r.stdout.split("SAID ")[-1]))
    assert os.listdir(jax_dir)
    assert [w for w, _ in said] == [{"prefill:4": "miss", "decode": "miss"}] \
        * 2 + [{"prefill:4": "disk", "decode": "disk"}]
    assert said[2][1] == said[0][1]


# ---------------------------------------------------------------------------
# the four choke points: warm boot = zero XLA retraces
# ---------------------------------------------------------------------------

def _training_workload(tr, plist, kv, x):
    """One optimizer step (op fwd + vjp + fused optimizer) and one
    flat-packed pushpull. No rng anywhere: rng-bearing executables are
    the documented XLA:CPU deserialize limitation."""
    with autograd.record():
        loss = plist[0].data().reshape(-1)[0] * 0
        for p in plist:
            loss = loss + (p.data() * x).sum()
    loss.backward()
    tr.step(1)
    vals = [nd.ones((4, 3)) for _ in range(3)]
    outs = [nd.zeros((4, 3)) for _ in range(3)]
    kv.pushpull_list(["a", "b", "c"], vals, outs=outs)


def test_warm_boot_zero_retraces_all_choke_points(cache_dir):
    x = nd.array(np.arange(12, dtype=np.float32).reshape(4, 3))
    params = gluon.ParameterDict()
    for j in range(4):
        p = params.get(f"w{j:03d}", shape=(4, 3), init="zeros")
        p.initialize()
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1}, kvstore="tpu")
    plist = [params[k] for k in sorted(params.keys())]
    kv = mx.kv.create("tpu")
    for k in ("a", "b", "c"):
        kv.init(k, nd.zeros((4, 3)))
    _training_workload(tr, plist, kv, x)    # cold: compiles everything
    _training_workload(tr, plist, kv, x)    # steady state
    table = profiler.compile_stats()
    families = ("op:", ":vjp", "fused:sgd", "kvstore:flat_pack",
                "kvstore:flat_unpack")
    for fam in families:
        assert any(fam in k for k in table), \
            f"choke point {fam!r} never exercised: {sorted(table)}"
    before = {k: v["misses"] for k, v in table.items()}
    s_before = cc.stats()
    cc.clear(memory=True)                   # fresh-replica simulation
    _training_workload(tr, plist, kv, x)    # warm boot
    after = profiler.compile_stats()
    retraced = {k: after[k]["misses"] - before.get(k, 0)
                for k in after if after[k]["misses"] > before.get(k, 0)}
    assert not retraced, f"warm boot retraced: {retraced}"
    s_after = cc.stats()
    assert s_after["misses"] == s_before["misses"]
    assert s_after["disk_hits"] - s_before["disk_hits"] >= 4


def test_second_predictor_boot_from_warm_dir_zero_retraces(cache_dir):
    # ONE exported artifact, two boots: the fleet scenario. (Two nets
    # built in-process get distinct gluon parameter names, hence distinct
    # call pytrees and — correctly — distinct fingerprints.)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(OUT_DIM))
    net.initialize()
    net(nd.array(np.zeros((1, IN_DIM), np.float32)))
    path = os.path.join(tempfile.mkdtemp(), "model")
    net.export(path)

    shapes = {"data": (1, IN_DIM)}
    x = np.random.RandomState(0).rand(3, IN_DIM).astype(np.float32)

    p1 = Predictor.from_artifact(path, bucket_sizes=(2, 4))
    kinds1 = p1.warmup(input_shapes=shapes)
    assert set(kinds1) == {2, 4}
    want = p1.predict({"data": x})[0]

    before = {k: v["misses"] for k, v in profiler.compile_stats().items()}
    s_before = cc.stats()
    cc.clear(memory=True)                   # replica #2 boots cold-in-RAM
    p2 = Predictor.from_artifact(path, bucket_sizes=(2, 4),
                                 input_shapes=shapes, prewarm=True)
    kinds2 = p2.warmup()
    assert all(k in ("disk", "hit") for k in kinds2.values()), kinds2
    got = p2.predict({"data": x})[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    after = profiler.compile_stats()
    retraced = {k: after[k]["misses"] - before.get(k, 0)
                for k in after if after[k]["misses"] > before.get(k, 0)}
    assert not retraced, f"second boot retraced: {retraced}"
    assert cc.stats()["misses"] == s_before["misses"]
    assert cc.stats()["disk_hits"] > s_before["disk_hits"]
    serve_rows = {k: v for k, v in after.items() if k.startswith("serve:exec[")}
    assert serve_rows and any(v["disk_hits"] > 0 for v in serve_rows.values())


# ---------------------------------------------------------------------------
# telemetry surfaces
# ---------------------------------------------------------------------------

def test_exec_cache_telemetry_in_dumps_and_prometheus(cache_dir):
    import json
    f = cc.cached_jit("test:telemetry", lambda a: a * a)
    x = np.ones((4,), np.float32)
    f(x)
    cc.clear(memory=True)
    f(x)                                    # one disk hit on the books
    j = json.loads(profiler.dumps(format="json"),
                   parse_constant=lambda t: pytest.fail(f"bare {t}"))
    ec = j["exec_cache"]
    assert ec["misses"] >= 1 and ec["disk_hits"] >= 1
    assert ec["bytes"] > 0
    assert j["compile"]["test:telemetry"]["disk_hits"] == 1
    table = profiler.dumps()
    assert "Executable cache (two-tier)" in table
    assert "exec_cache_disk_hits" in table
    text = profiler.render_prometheus()
    for fam in ("mxnet_exec_cache_hits_total", "mxnet_exec_cache_misses_total",
                "mxnet_exec_cache_disk_hits_total",
                "mxnet_exec_cache_evictions_total", "mxnet_exec_cache_bytes",
                "mxnet_exec_cache_entries"):
        assert f"# TYPE {fam} " in text, fam
    assert 'mxnet_compile_cache_disk_hits_total{key="test:telemetry"} 1' in text


def test_disk_tier_disabled_without_env(monkeypatch):
    monkeypatch.delenv("MXNET_EXEC_CACHE_DIR", raising=False)
    cc.clear(memory=True, stats=True)
    f = cc.cached_jit("test:no_disk", lambda a: a + 2.0)
    x = np.ones((3,), np.float32)
    np.testing.assert_allclose(np.asarray(f(x)), x + 2)
    assert cc.disk_stats() == {"dir": None, "entries": 0, "bytes": 0,
                               "budget": cc._disk_budget()}
    s = cc.stats()
    assert s["misses"] >= 1 and s["bytes"] == 0
    cc.clear(memory=True)
    np.testing.assert_allclose(np.asarray(f(x)), x + 2)   # recompile, no disk
    assert cc.stats()["disk_hits"] == 0
    cc.clear(memory=True, stats=True)
