"""Kernel autotuner (tune.py): search, persistence, and fused-kernel parity.

Coverage demanded by the autotune milestone:
  * a search runs at most once per (kernel, shape, dtype, device)
    fingerprint per process; later calls are memory hits,
  * persisted winners are deterministic — re-tuning the same signature
    from a cold store reproduces the same record,
  * a warm process re-loads winners from disk with ZERO re-searches
    (subprocess test, the acceptance criterion),
  * corrupted and stale-version winner files degrade to a re-tune with
    disk_errors counted — never a crash, never a stale winner,
  * the fused conv+BN+ReLU and BN-epilogue Pallas candidates match the
    unfused XLA reference numerically (fp32 tight, bf16 tolerant) under
    both forward and grad, in interpret mode on CPU,
  * the integrated FusedConvBNReLU / FusedBNAddReLU ops are bit-compatible
    with the unfused Convolution/BatchNorm/relu composition they replace,
  * the tuner is never unconditional: candidates only dispatch after
    winning a timed search, and a vanished winner degrades to XLA.
"""
import hashlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd, tune
from incubator_mxnet_tpu.parallel import fused_conv as fc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Fresh persistent store + zeroed counters; toy kernels registered
    during a test are dropped on the way out."""
    d = tmp_path / "exec_cache"
    monkeypatch.setenv("MXNET_EXEC_CACHE_DIR", str(d))
    tune.clear(memory=True, stats=True)
    before = set(tune._kernels)
    yield str(d)
    with tune._lock:
        for name in set(tune._kernels) - before:
            del tune._kernels[name]
    tune.clear(memory=True, stats=True)


def _store(tune_dir):
    return os.path.join(tune_dir, "tuned")


def _entries(tune_dir):
    d = _store(tune_dir)
    try:
        return sorted(f for f in os.listdir(d) if f.endswith(".mxtn"))
    except OSError:
        return []


# ---------------------------------------------------------------------------
# search + memory table
# ---------------------------------------------------------------------------

def test_search_once_then_memory_hits(tune_dir):
    calls = {"n": 0}

    def builder(args, kwargs):
        calls["n"] += 1
        return {}               # nothing offered: XLA wins trivially

    tune.register_kernel("t_once", builder)
    f = lambda x: x + x  # noqa: E731
    x = jnp.ones((4,))
    for _ in range(3):
        out = tune.tuned_call("t_once", f, x)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    s = tune.stats()
    assert s["searches"] == 1
    assert s["hits"] == 2
    assert calls["n"] == 1      # builder consulted only by the search
    assert tune.winner_for("t_once", x) == "xla"


def test_distinct_shapes_get_distinct_searches(tune_dir):
    tune.register_kernel("t_shapes", lambda a, k: {})
    f = lambda x: x * 2  # noqa: E731
    tune.tuned_call("t_shapes", f, jnp.ones((4,)))
    tune.tuned_call("t_shapes", f, jnp.ones((8,)))
    tune.tuned_call("t_shapes", f, jnp.ones((4,), jnp.bfloat16))
    assert tune.stats()["searches"] == 3
    assert len(_entries(tune_dir)) == 3


def test_candidate_must_win_the_race_never_unconditional(tune_dir):
    """A registered Pallas candidate is only dispatched after beating the
    XLA fallback in a timed search; a numerically-wrong candidate is
    disqualified no matter how fast it is."""
    ran = {"cand": 0}

    def wrong(x):
        ran["cand"] += 1
        return x * 3            # diverges from the fallback

    tune.register_kernel("t_wrong", lambda a, k: {"fast_but_wrong": wrong})
    f = lambda x: x + x  # noqa: E731
    x = jnp.ones((8,))
    out = tune.tuned_call("t_wrong", f, x)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert tune.winner_for("t_wrong", x) == "xla"
    rec = next(iter(tune.winners().values()))
    assert list(rec["rejected"]) == ["fast_but_wrong"]
    assert rec["rejected"]["fast_but_wrong"].startswith("mismatch:")
    assert ran["cand"] > 0      # it WAS timed/validated, then rejected
    s = tune.stats()
    assert (s["cand_mismatches"], s["cand_errors"], s["cand_lost"]) == \
        (1, 0, 0)


def test_raising_candidate_is_recorded_with_its_reason(tune_dir):
    """A candidate the compiler/runtime refuses is not a candidate that
    lost: the record keeps the exception's type and first line, and the
    three ways of not winning are counted apart."""
    def refused(x):
        raise NotImplementedError("Mosaic says no\nsecond line")

    def slow(x):
        time.sleep(0.005)
        return x + x

    tune.register_kernel(
        "t_raise", lambda a, k: {"refused": refused, "slow": slow})
    x = jnp.ones((8,))
    out = tune.tuned_call("t_raise", lambda x: x + x, x)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    (rec,) = tune.winners().values()
    assert rec["winner"] == "xla"
    assert rec["rejected"] == {
        "refused": "error: NotImplementedError: Mosaic says no"}
    assert "slow" in rec["timings_us"]
    s = tune.stats()
    assert (s["cand_errors"], s["cand_mismatches"], s["cand_lost"]) == \
        (1, 0, 1)


def test_xla_only_scope_withholds_the_race_and_counts_it(tune_dir):
    """Programs traced over several devices (and shape-only passes) take
    the XLA candidate without a search — visibly, under `withheld`."""
    ran = {"n": 0}

    def cand(x):
        ran["n"] += 1
        return x + x

    tune.register_kernel("t_held", lambda a, k: {"pallas": cand})
    x = jnp.ones((8,))
    with tune.xla_only("test: spans devices"):
        out = tune.tuned_call("t_held", lambda x: x + x, x)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    s = tune.stats()
    assert (s["withheld"], s["searches"], ran["n"]) == (1, 0, 0)
    tune.tuned_call("t_held", lambda x: x + x, x)     # scope closed: races
    assert tune.stats()["searches"] == 1 and ran["n"] > 0


def test_search_inside_a_jit_trace_really_runs(tune_dir):
    """tuned_call sites live inside traced op bodies. The search must
    execute there (compile-time eval), not be staged into the enclosing
    trace — where every candidate used to die on a tracer conversion and
    XLA won by default."""
    tune.register_kernel("t_traced", lambda a, k: {"twice": lambda x: x * 2})

    @jax.jit
    def f(x):
        return tune.tuned_call("t_traced", lambda x: x + x, x)

    np.testing.assert_allclose(np.asarray(f(jnp.ones((8,)))), 2.0)
    (rec,) = tune.winners().values()
    assert rec["rejected"] == {}
    assert set(rec["timings_us"]) == {"xla", "twice"}
    assert tune.stats()["cand_errors"] == 0


def test_winner_dispatches_and_vanished_winner_degrades(tune_dir):
    """Force a candidate win via the bench hook (the fallback pays a host
    sleep only while being timed), then yank the candidate from the
    builder: dispatch must degrade to XLA with a fallback counted."""
    offered = {"on": True}
    cand = lambda x: x + x  # noqa: E731

    def builder(args, kwargs):
        return {"pallas": cand} if offered["on"] else {}

    def fallback(x):
        return x + x

    def bench(fn, *args, **kwargs):
        if fn is fallback:
            time.sleep(0.005)
        return fn(*args, **kwargs)

    tune.register_kernel("t_win", builder, bench=bench)
    x = jnp.ones((8,))
    out = tune.tuned_call("t_win", fallback, x)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert tune.winner_for("t_win", x) == "pallas"

    offered["on"] = False
    before = tune.stats()["fallbacks"]
    out = tune.tuned_call("t_win", fallback, x)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert tune.stats()["fallbacks"] == before + 1


def test_tuner_off_env_routes_to_fallback(tune_dir, monkeypatch):
    monkeypatch.setenv("MXNET_TUNE", "0")
    tune.register_kernel("t_off", lambda a, k: {"c": lambda x: x})
    out = tune.tuned_call("t_off", lambda x: x + 1, jnp.zeros((2,)))
    np.testing.assert_allclose(np.asarray(out), 1.0)
    s = tune.stats()
    assert s["searches"] == 0 and s["fallbacks"] == 1
    assert _entries(tune_dir) == []


# ---------------------------------------------------------------------------
# persistence: determinism, warm reload, corruption, staleness
# ---------------------------------------------------------------------------

def test_persisted_winner_is_deterministic(tune_dir):
    """Same signature, cold store -> identical record (winner + key +
    rejected set), independent of wall-clock timings."""
    def builder(args, kwargs):
        return {"wrong": lambda x: x * 5}    # always disqualified

    tune.register_kernel("t_det", builder)
    f = lambda x: x + x  # noqa: E731
    x = jnp.ones((4, 4))

    tune.tuned_call("t_det", f, x)
    (rec1,) = tune.winners().values()
    tune.clear(memory=True, disk=True)
    tune.tuned_call("t_det", f, x)
    (rec2,) = tune.winners().values()
    for field in ("kernel", "key", "winner", "rejected", "space_version",
                  "backend", "device_kind"):
        assert rec1[field] == rec2[field]


def test_winner_reloads_from_disk_without_research(tune_dir):
    tune.register_kernel("t_disk", lambda a, k: {})
    f = lambda x: -x  # noqa: E731
    x = jnp.ones((3,))
    tune.tuned_call("t_disk", f, x)
    assert len(_entries(tune_dir)) == 1

    tune.clear(memory=True)             # simulated fresh process
    out = tune.tuned_call("t_disk", f, x)
    np.testing.assert_allclose(np.asarray(out), -1.0)
    s = tune.stats()
    assert s["searches"] == 1           # no second search
    assert s["disk_hits"] == 1


@pytest.mark.parametrize("damage", ["truncate", "garbage", "bitflip"])
def test_corrupt_winner_file_retunes(tune_dir, damage):
    tune.register_kernel("t_corrupt", lambda a, k: {})
    f = lambda x: x * 2  # noqa: E731
    x = jnp.ones((5,))
    tune.tuned_call("t_corrupt", f, x)
    (name,) = _entries(tune_dir)
    path = os.path.join(_store(tune_dir), name)
    raw = open(path, "rb").read()
    if damage == "truncate":
        open(path, "wb").write(raw[:20])
    elif damage == "garbage":
        open(path, "wb").write(b"not a winner file")
    else:
        body = bytearray(raw)
        body[-1] ^= 0xFF
        open(path, "wb").write(bytes(body))

    tune.clear(memory=True)
    out = tune.tuned_call("t_corrupt", f, x)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    s = tune.stats()
    assert s["disk_errors"] >= 1
    assert s["searches"] == 2           # re-tuned
    # and the store is healthy again
    tune.clear(memory=True)
    tune.tuned_call("t_corrupt", f, x)
    assert tune.stats()["searches"] == 2


def test_stale_space_version_retunes(tune_dir):
    """A checksum-valid file whose search-space version predates the
    registered spec is dropped and re-tuned (the version bump is how a
    kernel author invalidates every stale winner at once)."""
    tune.register_kernel("t_stale", lambda a, k: {}, version=2)
    f = lambda x: x + 1  # noqa: E731
    x = jnp.ones((6,))
    tune.tuned_call("t_stale", f, x)
    (name,) = _entries(tune_dir)
    path = os.path.join(_store(tune_dir), name)
    raw = open(path, "rb").read()
    off = len(tune._MAGIC)
    fp = raw[off:off + 64]
    rec = json.loads(raw[off + 130:])
    rec["space_version"] = 1            # forge an older-space winner
    body = json.dumps(rec, sort_keys=True).encode("utf-8")
    open(path, "wb").write(
        tune._MAGIC + fp + b"\n"
        + hashlib.sha256(body).hexdigest().encode("ascii") + b"\n" + body)

    tune.clear(memory=True)
    tune.tuned_call("t_stale", f, x)
    s = tune.stats()
    assert s["disk_errors"] == 1
    assert s["searches"] == 2


_WARM_BOOT_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
import numpy as np
from incubator_mxnet_tpu import nd, tune
x = nd.array(np.ones((2, 8, 8, 8), np.float32))
w = nd.array(np.ones((8, 8, 3, 3), np.float32))
c = nd.array(np.ones((8,), np.float32))
y = nd.FusedConvBNReLU(x, w, c, c, c, c, kernel=(3, 3), stride=(1, 1),
                       pad=(1, 1), num_filter=8)[0]
y.asnumpy()
s = tune.stats()
s["winner"] = tune.winner_for("conv_bn_relu", x._data, w._data, c._data,
                              c._data, k=3, pad_lo=(1, 1), pad_hi=(1, 1))
print(json.dumps(s))
"""


def test_warm_process_boot_zero_researches(tune_dir):
    """Acceptance criterion: a second process against a warm store
    performs ZERO searches — every winner deserializes from disk."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_EXEC_CACHE_DIR=tune_dir)

    def boot():
        r = subprocess.run(
            [sys.executable, "-c", _WARM_BOOT_SCRIPT.format(repo=REPO)],
            capture_output=True, text=True, env=env, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    cold = boot()
    assert cold["searches"] >= 1
    assert cold["winner"] is not None

    warm = boot()
    assert warm["searches"] == 0
    assert warm["disk_hits"] >= 1
    assert warm["winner"] == cold["winner"]


# ---------------------------------------------------------------------------
# fused-kernel parity (Pallas interpret mode on CPU)
# ---------------------------------------------------------------------------

def _grads(fn, args):
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32))  # noqa: E731
    return jax.grad(loss, argnums=tuple(range(len(args))))(*args)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                       ("bfloat16", 3e-2)])
def test_bn_epilogue_candidates_parity(monkeypatch, dtype, tol):
    """Every offered bn_add_act Pallas block config matches the unfused
    reference forward; gradients are exact by construction (the custom_vjp
    backward IS the reference vjp)."""
    monkeypatch.setenv("MXTPU_TUNE_INTERPRET", "1")
    r = np.random.RandomState(2)
    z = jnp.asarray(r.standard_normal((2, 8, 4, 4)), dtype)
    s = jnp.asarray(r.standard_normal(8), jnp.float32)
    b = jnp.asarray(r.standard_normal(8), jnp.float32)
    res = jnp.asarray(r.standard_normal((2, 8, 4, 4)), dtype)
    args = (z, s, b, res)

    ref = fc.bn_act_reference(*args)
    gref = _grads(lambda *a: fc.bn_act_reference(*a), args)
    cands = fc.bn_act_candidates(True, True)(args, {})
    assert cands, "interpret-mode candidates must be offered under the env"
    for name, fn in cands.items():
        out = fn(*args)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol, err_msg=name)
        for g, gr in zip(_grads(fn, args), gref):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(gr, np.float32),
                rtol=1e-6, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("dtype,tol", [("float32", 5e-5),
                                       ("bfloat16", 3e-2)])
def test_conv_bn_relu_candidates_parity(monkeypatch, dtype, tol):
    monkeypatch.setenv("MXTPU_TUNE_INTERPRET", "1")
    r = np.random.RandomState(3)
    x = jnp.asarray(r.standard_normal((2, 8, 12, 12)), dtype)
    w = jnp.asarray(r.standard_normal((16, 8, 3, 3)), dtype)
    s = jnp.asarray(r.standard_normal(16), jnp.float32)
    b = jnp.asarray(r.standard_normal(16), jnp.float32)
    kw = {"k": 3, "pad_lo": (1, 1), "pad_hi": (1, 1)}
    args = (x, w, s, b)

    ref = fc.conv_bn_relu_reference(x, w, s, b, 3, (1, 1), (1, 1))
    gref = _grads(
        lambda *a: fc.conv_bn_relu_reference(*a, 3, (1, 1), (1, 1)), args)
    cands = fc.conv_bn_relu_candidates(args, kw)
    assert cands
    variants = {n.split("_")[1] for n in cands}
    assert variants == {"patch", "taps"}
    for name, fn in cands.items():
        out = fn(*args, **kw)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol, err_msg=name)
        for g, gr in zip(_grads(lambda *a: fn(*a, **kw), args), gref):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(gr, np.float32),
                rtol=1e-6, atol=1e-6, err_msg=name)


def test_interpret_candidates_gated_off_by_default(monkeypatch):
    """Off-TPU without the opt-in env, candidate sets are empty: CPU runs
    never pay a Pallas interpret-mode timing race."""
    monkeypatch.delenv("MXTPU_TUNE_INTERPRET", raising=False)
    if jax.default_backend() == "tpu":
        pytest.skip("gate only applies off-TPU")
    z = jnp.ones((2, 8, 4, 4))
    s = jnp.ones(8)
    assert fc.bn_act_candidates(True, False)((z, s, s), {}) == {}
    x = jnp.ones((2, 8, 12, 12))
    w = jnp.ones((16, 8, 3, 3))
    assert fc.conv_bn_relu_candidates(
        (x, w, jnp.ones(16), jnp.ones(16)),
        {"k": 3, "pad_lo": (1, 1), "pad_hi": (1, 1)}) == {}


# ---------------------------------------------------------------------------
# integrated ops: fused == unfused composition (CPU dispatches the xla
# winner, so these are exact)
# ---------------------------------------------------------------------------

def _rand(shape, seed):
    return np.random.RandomState(seed).standard_normal(shape).astype(
        np.float32)


def test_fused_conv_bn_relu_op_matches_composition():
    x = nd.array(_rand((2, 8, 10, 10), 0))
    w = nd.array(_rand((16, 8, 3, 3), 1))
    gamma = nd.array(np.abs(_rand((16,), 2)) + 0.5)
    beta = nd.array(_rand((16,), 3))
    mean = nd.array(_rand((16,), 4))
    var = nd.array(np.abs(_rand((16,), 5)) + 0.5)

    conv = nd.Convolution(x, w, kernel=(3, 3), stride=(1, 1), pad=(1, 1),
                          num_filter=16, no_bias=True)
    bn_out = nd.BatchNorm(conv, gamma, beta, mean, var)[0]
    ref = nd.relu(bn_out).asnumpy()

    got = nd.FusedConvBNReLU(x, w, gamma, beta, mean, var,
                             kernel=(3, 3), stride=(1, 1), pad=(1, 1),
                             num_filter=16)[0].asnumpy()
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)


def test_fused_bn_add_relu_op_matches_composition():
    z = nd.array(_rand((2, 16, 6, 6), 10))
    res = nd.array(_rand((2, 16, 6, 6), 11))
    gamma = nd.array(np.abs(_rand((16,), 12)) + 0.5)
    beta = nd.array(_rand((16,), 13))
    mean = nd.array(_rand((16,), 14))
    var = nd.array(np.abs(_rand((16,), 15)) + 0.5)

    ref = nd.relu(nd.BatchNorm(z, gamma, beta, mean, var)[0] + res).asnumpy()
    got = nd.FusedBNAddReLU(z, gamma, beta, mean, var, res)[0].asnumpy()
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)


def test_resnet_block_fused_path_matches_oracle(monkeypatch):
    """One gluon residual block, same instance, fused path vs the
    layer-by-layer oracle: forward and input gradient agree in eval and
    train, including the running-stat writes."""
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import \
        BasicBlockV1

    blk = BasicBlockV1(channels=8, stride=1)
    blk.initialize(mx.init.Xavier())
    xh = _rand((2, 8, 6, 6), 20)

    stats0 = None

    def run(fused, train):
        monkeypatch.setenv("MXTPU_FUSED_BLOCK", "1" if fused else "0")
        x = nd.array(xh)
        if not train:
            return blk(x).asnumpy(), None, None
        # each train run starts from the same running stats (a forward
        # mutates them; without the reset the second run would compound)
        for k, v in blk.collect_params().items():
            if "running" in k:
                v.set_data(nd.array(stats0[k]))
        x.attach_grad()
        with autograd.record():
            y = blk(x)
        y.backward()
        stats = {k: v.data().asnumpy() for k, v in
                 blk.collect_params().items() if "running" in k}
        return y.asnumpy(), x.grad.asnumpy(), stats

    y_ref, _, _ = run(False, False)
    y_fused, _, _ = run(True, False)
    np.testing.assert_allclose(y_fused, y_ref, rtol=0, atol=1e-6)

    stats0 = {k: v.data().asnumpy() for k, v in
              blk.collect_params().items() if "running" in k}

    y_ref, g_ref, st_ref = run(False, True)
    y_fused, g_fused, st_fused = run(True, True)
    np.testing.assert_allclose(y_fused, y_ref, rtol=0, atol=1e-6)
    np.testing.assert_allclose(g_fused, g_ref, rtol=0, atol=1e-6)
    for k in st_ref:
        np.testing.assert_allclose(st_fused[k], st_ref[k], rtol=1e-5,
                                   atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# where the race lives: never in a train step, on one device as on a mesh;
# still in a forward pass that is the whole program
# ---------------------------------------------------------------------------

def _residual_net():
    """conv + BatchNorm, then a residual block: with MXTPU_FUSED_BLOCK at
    its default the forward pass goes through `bn_apply`, `bn_act` and
    `bn_add_act` sites in training, `conv_bn_relu` besides in inference."""
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import \
        BasicBlockV1
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1, use_bias=False, in_channels=3),
            gluon.nn.BatchNorm(in_channels=8),
            BasicBlockV1(channels=8, stride=1, in_channels=8),
            gluon.nn.GlobalAvgPool2D(), gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    return net


def _train_step(mesh_devices):
    from incubator_mxnet_tpu.parallel import TrainStep, make_mesh
    mesh = (None if mesh_devices is None else
            make_mesh({"dp": mesh_devices}, jax.devices()[:mesh_devices]))

    def loss_fn(out, label):
        logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, label.astype(jnp.int32)[:, None], 1))

    return TrainStep(_residual_net(), loss_fn, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                     mesh=mesh, example_inputs=[nd.ones((8, 3, 8, 8))])


_BATCH = (np.ones((8, 3, 8, 8), np.float32), np.zeros((8,), np.int32))


@pytest.mark.parametrize("mesh_devices", [None, 1, 8],
                         ids=["no-mesh", "one-device-mesh", "dp8"])
def test_train_step_takes_xla_at_every_tuned_site(tune_dir, mesh_devices):
    """One behaviour for a train step, whatever the chip count: no search,
    no winner record, every tuned site counted under `withheld`."""
    step = _train_step(mesh_devices)
    tune.clear(memory=True, stats=True)     # construction's shape-only pass
    loss = float(step(*_BATCH))
    assert np.isfinite(loss)
    s = tune.stats()
    assert s["searches"] == 0 and s["withheld"] > 0, s
    assert tune.winners() == {}


def test_train_step_compiles_no_pallas_call_when_candidates_are_offered(
        tune_dir, monkeypatch):
    """With the Pallas candidates on offer (interpret mode, as on a chip),
    the one-device step's program still holds none of them."""
    monkeypatch.setenv("MXTPU_TUNE_INTERPRET", "1")
    step = _train_step(None)
    x, y = (jnp.asarray(a) for a in _BATCH)
    jaxpr = jax.make_jaxpr(step._step_fn)(
        step.params, step.opt_state, jax.random.PRNGKey(0), 0, x, y)
    assert "pallas_call" not in str(jaxpr)
    assert "conv_general_dilated" in str(jaxpr)
    assert tune.stats()["searches"] == 0 and tune.winners() == {}


def test_inference_pass_of_the_same_net_still_races(tune_dir, monkeypatch):
    """Where a forward pass is the whole program the race stays: a jitted
    inference pass times the Pallas candidates at its tuned sites."""
    monkeypatch.setenv("MXTPU_TUNE_INTERPRET", "1")
    net = _residual_net()
    net.hybridize()
    out = net(nd.array(_BATCH[0])).asnumpy()
    assert np.all(np.isfinite(out))
    s = tune.stats()
    assert s["searches"] > 0 and s["withheld"] == 0, s
    raced = {r["kernel"] for r in tune.winners().values()
             if any(n.startswith("pallas") for n in r["timings_us"])}
    assert "conv_bn_relu" in raced, tune.winners()


# ---------------------------------------------------------------------------
# a 3x3 s1 p1 Convolution is plain XLA: no tuned site under it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 8, 16, 8), (2, 24, 8, 16),
                                   (3, 16, 7, 16)])
def test_conv_3x3_is_xla_and_touches_no_tuner_counter(tune_dir, shape):
    n, ci, h, co = shape
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, ci, h, h).astype(np.float32))
    w = jnp.asarray(rng.randn(co, ci, 3, 3).astype(np.float32) * 0.1)
    go = jnp.asarray(rng.randn(n, co, h, h).astype(np.float32))

    def plain(x_, w_):
        return jax.lax.conv_general_dilated(
            x_, w_, window_strides=(1, 1), padding=[(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            preferred_element_type=jnp.float32)

    def op(x_, w_):
        return mx.ops.nn_ops.convolution.fn(
            x_, w_, kernel=(3, 3), stride=(1, 1), pad=(1, 1), num_filter=co,
            no_bias=True)

    before = tune.stats()
    out, vjp = jax.vjp(op, x, w)
    dx, dw = vjp(go)
    assert tune.stats() == before
    want, vjp = jax.vjp(plain, x, w)
    dxr, dwr = vjp(go)
    for got, ref in ((out, want), (dx, dxr), (dw, dwr)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # the same primitive with the same parameters, and nothing beside it
    # but the op's trailing astype (a no-op in f32)
    eqns = jax.make_jaxpr(op)(x, w).eqns
    ref_eqn, = jax.make_jaxpr(plain)(x, w).eqns
    convs = [e for e in eqns if e.primitive.name == "conv_general_dilated"]
    assert len(convs) == 1 and convs[0].params == ref_eqn.params
    assert {e.primitive.name for e in eqns} <= {"conv_general_dilated",
                                                "convert_element_type"}


def test_gluon_conv_net_trains_on_the_xla_path(tune_dir):
    """A Conv2D net trains through autograd on the path that remains, and
    its weight gradient is lax.conv_general_dilated's own."""
    rng = np.random.RandomState(0)
    xh = rng.rand(2, 4, 8, 8).astype(np.float32)
    wh = rng.randn(4, 4, 3, 3).astype(np.float32) * 0.1
    x, w = nd.array(xh), nd.array(wh)
    w.attach_grad()
    with autograd.record():
        y = nd.Convolution(x, w, num_filter=4, kernel=(3, 3), pad=(1, 1),
                           no_bias=True)
        loss = (y * y).sum()
    loss.backward()

    def ref_loss(w_):
        return jnp.sum(jnp.square(jax.lax.conv_general_dilated(
            jnp.asarray(xh), w_, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            preferred_element_type=jnp.float32)))
    np.testing.assert_allclose(w.grad.asnumpy(),
                               np.asarray(jax.grad(ref_loss)(jnp.asarray(wh))),
                               rtol=1e-4, atol=1e-3)
    assert tune.stats()["searches"] == 0


@pytest.mark.parametrize("reader,name", [
    ("getenv_bool", "MXTPU_FUSED_CONV_BWD"),
    ("getenv_str", "MXTPU_CONV_BWD_KERNEL")])
def test_the_conv_backward_knobs_are_undeclared(reader, name):
    from incubator_mxnet_tpu import util
    from incubator_mxnet_tpu.base import MXNetError
    assert name not in util.ENV_VARS and len(util.ENV_VARS) == 82
    with pytest.raises(MXNetError, match="is not declared"):
        getattr(util, reader)(name)
