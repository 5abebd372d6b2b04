"""Records the fixture `tests/perfbench/data/scoped1.xplane.pb.gz` and its
`scoped1.json`, on the chip:

    chiprun -- python3 tests/perfbench/make_scoped1.py

then copy `chiprun_out/scoped1/scoped1.*` into `tests/perfbench/data/`.

A two-layer toy in plain jax, so the fixture depends on nothing of the
program but the three flash kernels' names: `forward` (embed, layer0 and
layer1 under `jax.checkpoint`, each `attn` with one small causal
`flash_attention` call of (BH, T, D) = (8, 256, 64) and `mlp`; a
`BatchNorm`-scoped normalisation over batch and positions that reads an
argument and writes a result of the program (XLA stages even those through
on-chip memory at this size: the recorded `bn_hbm_roofline` is 0.0); `logits`), `loss`, `optimizer`, four steps in one traced
slice. `scoped1.json` keeps what this
run itself read (`perfbench.op_scopes`, `trace_reduce`), the peaks it used
and the required work of the flash calls, which
`tests/perfbench/test_pb_scopes.py` works out again from the trace.
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
STEPS = 4
B, T, H, HD, F, V = 4, 256, 2, 64, 256, 512
D = H * HD


def build():
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel.flash_attention import flash_attention

    def init(key):
        ks = iter(jax.random.split(key, 16))
        w = lambda *s: (jax.random.normal(next(ks), s, jnp.float32)
                        * s[0] ** -0.5).astype(jnp.bfloat16)
        return {"embed": w(V, D), "layers": [
            {"wqkv": w(D, 3 * D), "wo": w(D, D), "w_in": w(D, F),
             "w_out": w(F, D)} for _ in range(2)]}

    def layer(p, x):
        with jax.named_scope("attn"):
            q, k, v = jnp.split((x @ p["wqkv"]).reshape(B, T, 3 * H, HD), 3,
                                axis=2)
            x = x + flash_attention(q, k, v, causal=True).reshape(B, T, D) \
                @ p["wo"]
        with jax.named_scope("mlp"):
            return x + jax.nn.gelu(x @ p["w_in"]) @ p["w_out"]

    def loss_of(params, tokens, targets, extra):
        with jax.named_scope("forward"):
            with jax.named_scope("embed"):
                x = params["embed"][tokens]
            for i, p in enumerate(params["layers"]):
                with jax.named_scope(f"layer{i}"):
                    x = jax.checkpoint(layer)(p, x)
            with jax.named_scope("BatchNorm"):
                # `extra` is an argument of the program and `normed` a result,
                # so they live in HBM; what the toy computes for itself stays
                # in on-chip memory
                x32 = x.astype(jnp.float32) + extra
                mean = jnp.mean(x32, axis=(0, 1))
                var = jnp.var(x32, axis=(0, 1))
                normed = (x32 - mean) * jax.lax.rsqrt(var + 1e-5)
                x = normed.astype(x.dtype)
            with jax.named_scope("logits"):
                logits = (x @ params["embed"].T).astype(jnp.float32)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, targets[..., None], axis=-1)), normed

    @jax.jit
    def step(params, tokens, targets, extra):
        (loss, normed), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params, tokens, targets, extra)
        with jax.named_scope("optimizer"):
            params = jax.tree.map(
                lambda w, g: (w.astype(jnp.float32) - 0.01
                              * g.astype(jnp.float32)).astype(w.dtype),
                params, grads)
        return params, loss, normed

    key = jax.random.PRNGKey(24)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (B, T), 0, V,
                                jnp.int32)
    extra = jax.random.normal(jax.random.fold_in(key, 2), (B, T, D),
                              jnp.float32)
    return step, init(key), (tokens, jnp.roll(tokens, -1, 1), extra)


def main(out_dir):
    import jax
    from perfbench import cells, flops, op_scopes, trace_reduce
    from perfbench.spans import traced_slice
    step, params, batch = build()
    for _ in range(3):
        params, loss, _ = step(params, *batch)
    loss.block_until_ready()
    # the readers look under <root>/perfbench/out/<cell>/trace
    cell = cells.Cell(name="scoped1", chips=1, config={}, traffic={},
                      end_to_end=[], per_layer=[], root=out_dir)
    cell_dir = os.path.join(out_dir, "perfbench", "out", cell.name)
    shutil.rmtree(cell_dir, ignore_errors=True)
    os.makedirs(cell_dir)

    def body():
        p = params
        for _ in range(STEPS):
            p, loss, _ = step(p, *batch)
        loss.block_until_ready()
    _, anchor = traced_slice(os.path.join(cell_dir, "trace"), body)
    path = trace_reduce.find_xplane(os.path.join(cell_dir, "trace"))
    with open(path, "rb") as f, gzip.open(
            os.path.join(out_dir, "scoped1.xplane.pb.gz"), "wb", 9) as g:
        g.write(f.read())
    dev = jax.devices()[0]
    peaks = flops.device_peaks(dev.device_kind) if dev.platform == "tpu" \
        else flops.device_peaks("TPU v5 lite")      # a rehearsal's numbers
    summary = trace_reduce.reduce_trace(trace_reduce.load(path))
    run = {"cell": cell, "peaks": peaks, "trace": summary, "driver": {}}
    names = [m["name"] for m in cells.load_benchmark()["per_layer"]
             if os.path.isfile(os.path.join(
                 ROOT, "perfbench", "layer_metrics", m["name"] + ".py"))]
    read = {n: cells.layer_metric_reader(n)(run) for n in names}
    reduced = op_scopes.of(run) or {}
    record = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "steps": STEPS, "flash_shape": [B * H, T, HD], "peaks": peaks,
        "anchor": anchor, "busy_s": summary["busy_s"],
        "window_s": summary["window_s"], "read": read,
        "scoped_busy_s": reduced.get("busy_s"),
        "rows": len(reduced.get("rows", []))}
    with open(os.path.join(out_dir, "scoped1.json"), "w") as f:
        json.dump(record, f, indent=1)
    size = os.path.getsize(os.path.join(out_dir, "scoped1.xplane.pb.gz"))
    print(json.dumps(record, indent=1))
    print(f"[scoped1] {size} bytes gzipped (the fixture stays under 100 KB)")
    return 0 if size < 100_000 else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "scoped1")))
