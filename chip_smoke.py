#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py               the check: TPU required
    python3 chip_smoke.py --rehearse    tiny sizes, CPU, interpret kernels:
                                        proves the SCRIPT, never the chip

Three legs drive the main paths once, through the entry points a user
calls, at the full width of a model the repo supports (random weights
from a seed, depth as published):

  train  ResNet-50 v1, batch 128/chip, bf16, via train_imagenet.py's
         --kv-store tpu branch (parallel.TrainStep, one dispatch/step)
  lm     TransformerLM 12 x d1024, seq 2048, batch 32, bf16, remat, flash
         attention, through make_train_step(mesh)
  serve  one ModelServer over HTTP: /predict from an exported ResNet-50
         (Predictor.from_artifact, ladder prewarmed) against the Gluon
         net; /generate from a DecodeScheduler over a DecodePredictor at
         16 heads x 128, vocab 50,304, against the sequential oracle

One process per chip: this parent imports neither jax nor the package
and runs the legs as children, one after another. A leg that raises, a
kernel the compiler refuses, a hidden fallback counter that moved, or a
platform other than `tpu` ends the run non-zero with no result line.
The timings printed are set-up information, not performance.

Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
LEGS = ("train", "lm", "serve")
BUDGET_S = 1150          # the contract allows 1200 s, compilation included

FULL = {
    "train": dict(network="resnet50_v1", batch_per_chip=128, image=224,
                  classes=1000, dtype="bfloat16", steps=8, lr=0.02),
    "lm": dict(layers=12, d_model=1024, heads=16, d_ff=4096, vocab=32000,
               seq=2048, batch_per_replica=32, steps=4, check_seq=512),
    "serve": dict(network="resnet50_v1", image=224, classes=1000,
                  buckets=(1, 4), heads=16, head_dim=128, vocab=50304,
                  page_size=16, slots=16, num_pages=2048,
                  pages_per_seq=40, prompt_buckets=(32, 128, 512),
                  prompt_lens=(5, 40, 200, 500), new_tokens=32),
}
# --rehearse: the same code at sizes a CPU finishes in about a minute
REHEARSAL = {
    "train": dict(network="resnet18_v1", batch_per_chip=4, image=32,
                  classes=10, dtype="float32", steps=5, lr=0.02),
    # heads of 64 as on the chip, two to a tp shard: the kernels' paired
    # layout, with no transpose round them
    "lm": dict(layers=2, d_model=256, heads=4, d_ff=512, vocab=128,
               seq=64, batch_per_replica=2, steps=4, check_seq=64),
    "serve": dict(network="resnet18_v1", image=32, classes=10,
                  buckets=(1, 4), heads=2, head_dim=8, vocab=64,
                  page_size=4, slots=4, num_pages=64, pages_per_seq=12,
                  prompt_buckets=(4, 16), prompt_lens=(3, 7, 12),
                  new_tokens=8),
}


# ---------------------------------------------------------------------------
# parent: no jax, no package — a parent that touched jax would hold the chip
# ---------------------------------------------------------------------------

def _kill_group(proc):
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def parent(rehearse):
    deadline = time.monotonic() + BUDGET_S
    env = dict(os.environ)
    if rehearse:
        print("=" * 72 + "\nREHEARSAL: tiny sizes on the CPU with "
              "interpret-mode kernels. This checks chip_smoke.py itself "
              "and says NOTHING about the chip.\n" + "=" * 72, flush=True)
        env.update(JAX_PLATFORMS="cpu", MXTPU_TUNE_INTERPRET="1")
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    results = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        for leg in LEGS:
            out = os.path.join(tmp, leg + ".json")
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--leg", leg, "--result", out]
            if rehearse:
                cmd.append("--rehearse")
            proc = subprocess.Popen(cmd, env=env, start_new_session=True)
            try:
                rc = proc.wait(timeout=max(deadline - time.monotonic(), 1))
            except subprocess.TimeoutExpired:
                print(f"[smoke] leg {leg}: out of time "
                      f"({BUDGET_S}s for all legs)", flush=True)
                return 124
            finally:
                _kill_group(proc)       # and whatever the leg left behind
            if rc != 0:
                print(f"[smoke] leg {leg} FAILED (exit code {rc})",
                      flush=True)
                return rc if 0 < rc < 256 else 1
            with open(out) as f:
                results[leg] = json.load(f)
    devices = [r["device"] for r in results.values()]
    if any(d != devices[0] for d in devices):
        print(f"[smoke] legs disagree on the device: {devices}", flush=True)
        return 1
    for leg, r in results.items():
        print(f"[smoke] {leg}: set-up {r['setup_s']:.1f}s, "
              f"run {r['run_s']:.1f}s", flush=True)
    if rehearse:
        print(json.dumps({"rehearsal": True, "legs_passed": list(results),
                          "device": devices[0]}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# what every leg does first and last
# ---------------------------------------------------------------------------

def begin(leg, rehearse):
    """Print the device before anything else; refuse anything but a TPU."""
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[smoke:{leg}] jax {jax.__version__}  platform={dev.platform}  "
          f"device_kind={dev.device_kind}  devices={device['count']}",
          flush=True)
    if rehearse:
        print(f"[smoke:{leg}] REHEARSAL — not a chip run", flush=True)
    elif dev.platform != "tpu":
        raise SystemExit(
            f"[smoke:{leg}] no TPU: jax came up on platform "
            f"{dev.platform!r}. chip_smoke.py checks the chip; "
            f"`--rehearse` rehearses the script on the CPU.")
    from incubator_mxnet_tpu import native
    lib = native.load()         # builds libmxtpu.so from the checkout
    print(f"[smoke:{leg}] native library: " + (
        "ABSENT, pure python (not on the synthetic-data path)"
        if lib is None else
        f"built and loaded (jpeg decode: {bool(lib.has_jpeg)})"),
        flush=True)
    if not rehearse:
        # the copies of the rule `interpret = backend != "tpu"`
        import importlib
        for name in ("parallel.flash_attention", "parallel.fused_conv"):
            mod = importlib.import_module("incubator_mxnet_tpu." + name)
            assert mod._interpret() is False, f"{name} would interpret"
        assert jax.default_backend() == "tpu"   # compression.py, rtc.py
    return device, _cache_entries()


def _cache_entries():
    import jax
    d = jax.config.jax_compilation_cache_dir
    try:
        return d, len(os.listdir(d))
    except OSError:
        return d, 0


def finish(leg, device, cache_before, setup_s, run_s, result_path):
    """Print what set-up cost and every counter a fallback could hide
    behind; raise if one moved. Then write the leg's result."""
    import jax
    from incubator_mxnet_tpu import compile_cache, profiler, tune
    stats = profiler.compile_stats()
    misses = sum(r["misses"] for r in stats.values())
    hits = sum(r["hits"] for r in stats.values())
    top = sorted(stats.items(), key=lambda kv: -kv[1]["compile_ms"])[:6]
    print(f"[smoke:{leg}] set-up {setup_s:.1f}s (init + tuner search + "
          f"compile + first call) | run {run_s:.1f}s", flush=True)
    print(f"[smoke:{leg}] tracked compiles: {misses} misses, {hits} hits "
          f"over {len(stats)} keys; slowest: "
          + ", ".join(f"{k} x{r['misses']} {r['compile_ms'] / 1e3:.1f}s"
                      for k, r in top), flush=True)
    recs = sorted(tune.winners().values(),
                  key=lambda r: (r["kernel"], r["key"]))
    for r in recs:
        print(f"[smoke:{leg}] tune {r['kernel']} [{r['key']}] -> "
              f"{r['winner']}  timings_us={r['timings_us']}  "
              f"rejected={r['rejected']}", flush=True)
    tstats, cstats = tune.stats(), compile_cache.stats()
    print(f"[smoke:{leg}] tune.stats {tstats}", flush=True)
    print(f"[smoke:{leg}] compile_cache.stats {cstats}", flush=True)
    cdir, after = _cache_entries()
    print(f"[smoke:{leg}] jax compilation cache {cdir}: "
          f"{cache_before[1]} -> {after} entries", flush=True)
    peaks = {}
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        peaks[str(d.id)] = ms.get("peak_bytes_in_use")
    print(f"[smoke:{leg}] peak bytes in use per device: {peaks}",
          flush=True)

    refused = {f"{r['kernel']}:{n}": why for r in recs
               for n, why in r["rejected"].items()
               if why.startswith("error")}
    problems = []
    if refused:
        problems.append(f"tuner candidates refused: {refused}")
    for name in ("fallbacks", "disk_errors"):
        if tstats[name]:
            problems.append(f"tune.{name} = {tstats[name]}")
    for name in ("fallbacks", "disk_errors"):
        if cstats[name]:
            problems.append(f"compile_cache.{name} = {cstats[name]}")
    if problems:
        raise SystemExit(f"[smoke:{leg}] hidden fallback: "
                         + "; ".join(problems))
    with open(result_path, "w") as f:
        json.dump(dict(leg=leg, device=device, setup_s=setup_s,
                       run_s=run_s), f)
    print(f"[smoke:{leg}] PASSED", flush=True)


def check_spread(leg, arrays, n):
    """Work really is on every chip: each array has shards addressable
    on n distinct devices, and every device holds live bytes."""
    import jax
    for name, a in arrays.items():
        devs = {s.device for s in a.addressable_shards}
        assert len(devs) == n, \
            f"{name} lives on {len(devs)} of {n} devices: {devs}"
    for d in jax.local_devices():
        ms = d.memory_stats()
        if ms is not None:          # the CPU backend reports none
            assert ms["bytes_in_use"] > 0, f"device {d.id} holds nothing"
    print(f"[smoke:{leg}] spread over {n} devices: "
          + ", ".join(arrays), flush=True)


def check_losses(leg, losses):
    import math
    print(f"[smoke:{leg}] losses {[round(l, 4) for l in losses]}",
          flush=True)
    assert all(math.isfinite(l) for l in losses), "non-finite loss"
    assert losses[-1] < losses[0], "loss did not fall"


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg_train(cfg, rehearse, result_path):
    device, cache0 = begin("train", rehearse)
    import importlib.util
    import jax
    import numpy as np
    spec = importlib.util.spec_from_file_location(
        "train_imagenet", os.path.join(ROOT, "example",
                                       "image-classification",
                                       "train_imagenet.py"))
    entry = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(entry)

    n = device["count"]
    batch = cfg["batch_per_chip"] * n
    shape = (3, cfg["image"], cfg["image"])
    step, records = entry.main([
        "--network", cfg["network"], "--batch-size", str(batch),
        "--num-batches", str(cfg["steps"]), "--num-classes",
        str(cfg["classes"]), "--image-shape", ",".join(map(str, shape)),
        "--dtype", cfg["dtype"], "--lr", str(cfg["lr"]),
        "--kv-store", "device" if rehearse else "tpu",
        "--disp-batches", "1"])
    assert len(records) == cfg["steps"] >= 5
    check_losses("train", [r[2] for r in records])
    from incubator_mxnet_tpu import profiler
    row = profiler.compile_stats()["trainstep:sgd"]
    assert row["misses"] == 1, f"the step recompiled: {row}"
    if n > 1:
        assert step.mesh is not None and step.mesh.devices.size == n
        x, y = step._to_device([np.zeros((batch,) + shape, np.float32),
                                np.zeros((batch,), np.float32)])
        assert x.addressable_shards[0].data.shape[0] == batch // n
        first = next(iter(step.params))
        check_spread("train", {"batch": x, "labels": y,
                               f"param {first}": step.params[first]}, n)
    finish("train", device, cache0, setup_s=records[0][3],
           run_s=records[-1][3] - records[0][3], result_path=result_path)


def _lm_mesh_axes(n):
    if n == 1:
        return {"dp": 1}
    return {"dp": n // 2, "tp": 2} if n % 2 == 0 else {"dp": n}


def leg_lm(cfg, rehearse, result_path):
    device, cache0 = begin("lm", rehearse)
    import importlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                        TransformerLM)
    from incubator_mxnet_tpu.parallel import make_mesh
    from incubator_mxnet_tpu.parallel.ring_attention import \
        attention_reference
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")

    t0 = time.time()
    # the kernels agree with the reference on a small input, fwd and bwd
    rng = np.random.RandomState(0)
    hd = cfg["d_model"] // cfg["heads"]
    q, k, v = (jnp.asarray(rng.standard_normal(
        (2, cfg["check_seq"], 2, hd)), jnp.bfloat16) for _ in range(3))

    def grads(attn):
        return jax.grad(lambda *a: attn(*a, causal=True)
                        .astype(jnp.float32).sum(), argnums=(0, 1, 2))
    got = (fa.flash_attention(q, k, v, causal=True),) + \
        grads(fa.flash_attention)(q, k, v)
    want = (attention_reference(q, k, v, causal=True),) + \
        grads(attention_reference)(q, k, v)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=5e-2, atol=5e-2, err_msg=f"flash {name} vs reference")
    print("[smoke:lm] flash fwd/dq/dk/dv agree with attention_reference "
          f"at T={cfg['check_seq']} D={hd} bf16", flush=True)

    n = device["count"]
    axes = _lm_mesh_axes(n)
    mesh = make_mesh(axes)
    batch = cfg["batch_per_replica"] * axes["dp"]
    model = TransformerLM(TransformerConfig(
        vocab_size=cfg["vocab"], d_model=cfg["d_model"],
        n_heads=cfg["heads"], n_layers=cfg["layers"], d_ff=cfg["d_ff"],
        max_len=cfg["seq"], dtype="bfloat16", remat=True,
        flash_attention=True))
    step, shard_params, init_opt = model.make_train_step(
        mesh, lr=1e-3, use_sp=False)
    params = shard_params(model.init_params(jax.random.PRNGKey(0)))
    opt = init_opt(params)
    toks = rng.randint(0, cfg["vocab"], (batch, cfg["seq"])).astype(np.int32)
    data_sh = NamedSharding(mesh, P("dp"))
    tokens = jax.device_put(toks, data_sh)
    targets = jax.device_put(np.roll(toks, -1, 1), data_sh)
    print(f"[smoke:lm] mesh {axes}, batch {batch} x seq {cfg['seq']}, "
          f"{cfg['layers']} x d{cfg['d_model']}", flush=True)

    before = fa.dispatch_stats()
    losses, t_first = [], None
    for i in range(cfg["steps"]):
        params, opt, loss = step(params, opt, tokens, targets, i)
        losses.append(float(loss))
        t_first = t_first or time.time()
    t_end = time.time()
    assert cfg["steps"] >= 3
    check_losses("lm", losses)
    assert step._cache_size() == 1, "the step retraced"
    took = {k_: fa.dispatch_stats()[k_] - before[k_] for k_ in before}
    print(f"[smoke:lm] attention dispatch while tracing the step: {took}",
          flush=True)
    assert took["pallas"] > 0 and took["reference"] == 0, \
        "flash attention dropped to attention_reference"
    assert took["direct"] > 0 and took["transposed"] == 0, \
        "the step's q, k, v went through a transpose on their way to flash"
    run, square = took["causal_subblocks_run"], took["causal_subblocks_all"]
    print(f"[smoke:lm] causal score sub-blocks computed: {run} of {square}",
          flush=True)
    # from 256 positions on a grid block on the diagonal has sub-blocks to
    # skip; below that the whole square is one masked pass
    assert 0 < run <= square and (run < square or cfg["seq"] < 256), \
        "the causal walk inside the kernels did not engage"
    # a causal layer's backward is ONE kernel call at every length whose dq
    # a head fits the chip's VMEM (PR 39; up to 1,024 positions since PR
    # 37); the dq / dkv pair only past that
    fused, pair = took["bwd_fused"], took["bwd_pair"]
    print(f"[smoke:lm] flash backward calls traced: {fused} alone, {pair} "
          "pairs", flush=True)
    assert (fused, pair) == (cfg["layers"], 0), \
        "a causal layer's backward is not the one call"
    # a block's checkpoint keeps the forward kernel's output and lse, so the
    # step holds the kernel once a layer, not once more in each backward
    fwds = str(jax.make_jaxpr(step)(params, opt, tokens, targets, 0)).count(
        "name=flash_fwd")
    print(f"[smoke:lm] flash_fwd calls in the step: {fwds} for "
          f"{cfg['layers']} layers", flush=True)
    assert fwds == cfg["layers"], \
        "a block's checkpoint runs its flash forward again"
    if n > 1:
        check_spread("lm", {"tokens": tokens,
                            "layer0_wq": params["layer0_wq"],
                            "embed": params["embed"]}, n)
        assert len({s.index for s in
                    params["layer0_wq"].addressable_shards}) == axes["tp"]
    finish("lm", device, cache0, setup_s=t_first - t0,
           run_s=t_end - t_first, result_path=result_path)


def _post(url, payload, timeout=300):
    import urllib.request
    req = urllib.request.Request(
        url, json.dumps(payload).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        if r.headers["Content-Type"] == "application/x-ndjson":
            return [json.loads(line) for line in r if line.strip()]
        return json.loads(r.read())


def _burst(fn, items):
    """fn(item) for every item at once; results in order, errors raised."""
    import threading
    out, errs = [None] * len(items), []

    def run(i):
        try:
            out[i] = fn(items[i])
        except Exception as e:          # noqa: BLE001 — re-raised below
            errs.append(e)
    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(items))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "request hung"
    if errs:
        raise errs[0]
    return out


def leg_serve(cfg, rehearse, result_path):
    device, cache0 = begin("serve", rehearse)
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.serve import (DecodePredictor, DecodeScheduler,
                                           ModelServer, Predictor)
    t0 = time.time()

    # -- /predict: exported ResNet against the Gluon net it came from ----
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    shape = (3, cfg["image"], cfg["image"])
    n_samples = max(cfg["buckets"])
    net = getattr(vision, cfg["network"])(classes=cfg["classes"])
    net.initialize(mx.init.Xavier(magnitude=2.0))
    net.hybridize()
    x = rng.standard_normal((n_samples,) + shape).astype(np.float32)
    want = net(mx.nd.array(x)).asnumpy()
    assert want.shape == (n_samples, cfg["classes"])
    assert np.isfinite(want).all()

    with tempfile.TemporaryDirectory(prefix="chip_smoke_model_") as tmp:
        prefix = os.path.join(tmp, cfg["network"])
        net.export(prefix)
        pred = Predictor.from_artifact(
            prefix, input_shapes={"data": (1,) + shape},
            bucket_sizes=cfg["buckets"], prewarm=True)
    assert pred.is_warm

    # -- /generate: DecodePredictor at real attention width --------------
    e = cfg["heads"] * cfg["head_dim"]

    def w(*s, scale):
        return (rng.standard_normal(s) * scale).astype(np.float32)
    dec = DecodePredictor(
        {"emb": w(cfg["vocab"], e, scale=1.0),
         "wq": w(e, e, scale=e ** -0.5), "wk": w(e, e, scale=e ** -0.5),
         "wv": w(e, e, scale=e ** -0.5), "wo": w(e, e, scale=e ** -0.5),
         "w_out": w(e, cfg["vocab"], scale=e ** -0.5)},
        num_heads=cfg["heads"], head_dim=cfg["head_dim"],
        vocab=cfg["vocab"], prompt_buckets=cfg["prompt_buckets"],
        page_size=cfg["page_size"], num_pages=cfg["num_pages"],
        max_pages_per_seq=cfg["pages_per_seq"], slots=cfg["slots"])
    print(f"[smoke:serve] decode warmup: {dec.warmup()}", flush=True)
    prompts = [rng.randint(1, cfg["vocab"], n).tolist()
               for n in cfg["prompt_lens"]]
    new = cfg["new_tokens"]

    oracle_sched = DecodeScheduler(dec, name="smoke-oracle")
    oracle_sched.start()
    try:        # the same predictor, one stream at a time
        oracle = [oracle_sched.submit(p, max_new_tokens=new)
                  .result(timeout=600) for p in prompts]
    finally:
        oracle_sched.stop()
    assert oracle_sched.allocator.live == 0
    assert all(len(t) == new for t in oracle)
    setup_s = time.time() - t0

    sched = DecodeScheduler(dec, name="smoke-decode")
    # a long batching window: a burst's multi-megabyte JSON bodies take
    # the handler threads a while to parse, and they should share a batch
    server = ModelServer(pred, decoder=sched, max_latency_ms=500.0,
                         default_deadline_ms=300000.0)
    host, port = server.start()
    base = f"http://{host}:{port}"
    t1 = time.time()
    try:
        def predict(i):
            body = _post(f"{base}/predict",
                         {"inputs": {"data": x[i].tolist()}})
            return np.asarray(body["outputs"][0], np.float32)

        def generate(p):
            rows = _post(f"{base}/generate",
                         {"prompt": p, "max_new_tokens": new,
                          "deadline_ms": 300000})
            assert rows[-1].get("done"), rows[-1]
            return [r["token"] for r in rows if "token" in r]

        # one request alone, a burst that pads up its bucket, a full one
        for group in ([0], list(range(1, n_samples)),
                      list(range(n_samples))):
            for i, got in zip(group, _burst(predict, group)):
                # f32 end to end on both sides; the two paths fuse BN
                # differently, so equal to rounding, not to the bit
                np.testing.assert_allclose(
                    got, want[i], rtol=2e-3, atol=2e-3 * np.abs(want).max(),
                    err_msg=f"/predict sample {i} in a burst of "
                            f"{len(group)}")
        snap = server.stats.snapshot()
        assert snap["responses_ok"] == 2 * n_samples and not snap["errors"]
        print(f"[smoke:serve] /predict: {2 * n_samples} requests in "
              f"bursts of 1, {n_samples - 1}, {n_samples} match the Gluon "
              f"net ({snap['batches_total']} batches, "
              f"{snap['padded_rows_total']} padded rows)", flush=True)

        streamed = _burst(generate, prompts)
        assert streamed == oracle, \
            f"/generate differs from the sequential oracle:\n" \
            f"{streamed}\n{oracle}"
        print(f"[smoke:serve] /generate: {len(prompts)} concurrent "
              f"streams, prompt lengths {cfg['prompt_lens']}, {new} new "
              f"tokens each, equal to the sequential greedy decode",
              flush=True)
    finally:
        server.stop()
    run_s = time.time() - t1
    assert sched.allocator.live == 0, "KV pages leaked"
    finish("serve", device, cache0, setup_s=setup_s, run_s=run_s,
           result_path=result_path)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU with interpret kernels; "
                         "checks this script, says nothing about the chip")
    ap.add_argument("--leg", choices=LEGS, help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.leg is None:
        return parent(args.rehearse)
    sys.path.insert(0, ROOT)
    cfg = (REHEARSAL if args.rehearse else FULL)[args.leg]
    {"train": leg_train, "lm": leg_lm, "serve": leg_serve}[args.leg](
        cfg, args.rehearse, args.result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
