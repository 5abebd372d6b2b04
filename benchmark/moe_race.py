"""The race for the expert layer's grouped products: the builder's tool,
run by hand on the chip, never by the benchmark.

    python benchmark/moe_race.py [--rows 73728 --d 2048 --f 1792 --held 8]

Over `rows` sorted rows, with the groups near uniform as a seeded router
leaves them and the spare rows with the last, the routed part's two grouped
products (gate and up as one, then down) are raced two ways.

- A call at a time, under each candidate rule for the megablox tiles: the
  forward `gmm`, the rows' gradient (`gmm` with each matrix transposed:
  "d_lhs") and the matrices' (`tgmm`), each timed alone and CHECKED against
  a float32 product a group at a time at `highest` on the same bfloat16
  operands (`rel_err`: the worst error over the largest value; what Mosaic
  compiled, not the interpreter). `layer_us` adds them up as a remat step
  runs them: each forward twice, each gradient once. "former" is the rule
  the package had before (widths from 1,024, 512, 256; the rows' gradient
  on the forward's tiles), "program" the package's own (`parallel/moe._gmm_tiling`), the
  others every call tiled from its own shape at a fixed row tile.
- The layer's value and gradient whole: the program's
  (`parallel/moe.grouped_matmul`), `lax.ragged_dot`, and the same rows
  through one expert (the floor).

PERF.md section 6 has the readings."""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _former(m, k, n, groups):
    def fit(size, most):
        return next((t for t in (most, 512, 256, 128)
                     if t <= most and size % t == 0), size)
    tiles = fit(m, 256), fit(k, 1024), fit(n, 1024)
    return tiles, tiles, tiles


def _own(rows):
    """Every call from its own shape, `rows` rows a tile."""
    from incubator_mxnet_tpu.parallel.moe import _width

    def rule(m, k, n, groups):
        return ((rows, _width(k), _width(n)), (rows, _width(n), _width(k)),
                (rows, _width(k), _width(n)))
    return rule


def _program(m, k, n, groups):
    from incubator_mxnet_tpu.parallel import moe
    return (moe._gmm_tiling(m, k, n, groups), moe._gmm_tiling(m, n, k, groups),
            moe._gmm_tiling(m, k, n, groups))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--d", type=int, default=3072)
    ap.add_argument("--f", type=int, default=1024)
    ap.add_argument("--held", type=int, default=8)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from incubator_mxnet_tpu.parallel import moe
    mb = moe._megablox_backend()          # the kernels' module
    R, d, f, G = args.rows, args.d, args.f, args.held
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (R, d), jnp.bfloat16)
    w1 = (jax.random.normal(ks[1], (G, d, 2 * f)) / d ** 0.5).astype(
        jnp.bfloat16)
    w2 = (jax.random.normal(ks[2], (G, f, d)) / f ** 0.5).astype(jnp.bfloat16)
    sizes = np.random.default_rng(0).multinomial(R * 5 // 8, [1 / G] * G)
    sizes[-1] += R - sizes.sum()
    group = jnp.asarray(sizes, jnp.int32)
    ends = np.cumsum(sizes)
    spans = list(zip(ends - sizes, ends))
    device = jax.devices()[0].device_kind
    interpret = moe._interpret()       # Pallas's interpreter off the chip

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        jax.block_until_ready(out)
        return 1e6 * (time.perf_counter() - t0) / args.reps, out

    def f32(a):
        return a.astype(jnp.float32)

    def mm(a, b):
        return jnp.matmul(f32(a), f32(b), precision=lax.Precision.HIGHEST)

    def rel_err(got, want):
        return float(jnp.max(jnp.abs(f32(got) - want)) /
                     jnp.max(jnp.abs(want)))

    # -- a call at a time --------------------------------------------------
    products = {"gate_in": (x, w1), "down": (
        (jax.random.normal(ks[3], (R, f)) / 2).astype(jnp.bfloat16), w2)}
    cots = {"gate_in": jax.random.normal(ks[4], (R, 2 * f), jnp.bfloat16),
            "down": jax.random.normal(ks[5], (R, d), jnp.bfloat16)}
    want = {}
    for name, (lhs, rhs) in products.items():
        grad = cots[name]
        want[name] = {
            "fwd": jnp.concatenate([mm(lhs[s:e], rhs[g])
                                    for g, (s, e) in enumerate(spans)]),
            "d_lhs": jnp.concatenate([mm(grad[s:e], rhs[g].T)
                                      for g, (s, e) in enumerate(spans)]),
            "tgmm": jnp.stack([mm(lhs[s:e].T, grad[s:e])
                               for s, e in spans])}

    # 1,024-row tiles run out of the chip's scoped memory wherever the
    # contraction takes more than one step, as matrix tiles beyond 1,024 x
    # 1,024 do: the row says so
    rules = {"former": _former, "program": _program, "own_256": _own(256),
             "own_512": _own(512), "own_1024": _own(1024)}
    for rule_name, rule in rules.items():
        row = {"rule": rule_name, "rows": R, "d": d, "f": f, "held": G,
               "device": device, "layer_us": 0.0}
        for name, (lhs, rhs) in products.items():
            grad = cots[name]
            m, (k, n) = R, rhs.shape[1:]
            t_fwd, t_dlhs, t_tgmm = rule(m, k, n, G)
            calls = {
                "fwd": (t_fwd, lambda a, b, t=t_fwd: mb.gmm(
                    a, b, group, a.dtype, t, interpret=interpret), lhs, rhs),
                "d_lhs": (t_dlhs, lambda a, b, t=t_dlhs: mb.gmm(
                    a, b, group, a.dtype, t, transpose_rhs=True,
                    interpret=interpret), grad, rhs),
                "tgmm": (t_tgmm, lambda a, b, t=t_tgmm: mb.tgmm(
                    a.swapaxes(0, 1), b, group, a.dtype, t,
                    interpret=interpret), lhs, grad)}
            for call, (tiles, fn, a, b) in calls.items():
                key = f"{name}.{call}"
                row[key + ".tiles"] = tiles
                try:
                    with jax.default_matmul_precision("default"):
                        us, out = timed(jax.jit(fn), a, b)
                except Exception as e:   # a tiling the chip refuses: say so
                    row[key + ".error"] = f"{type(e).__name__}: {str(e)[:200]}"
                    row["layer_us"] = None
                    continue
                row[key + "_us"] = us
                row[key + ".rel_err"] = rel_err(out, want[name][call])
                if row["layer_us"] is not None:
                    row["layer_us"] += us * (2 if call == "fwd" else 1)
        print(json.dumps(row), flush=True)

    # -- the layer's value and gradient whole ------------------------------
    def experts(x, w1, w2, mm):
        h = mm(x, w1, group)
        h = (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(x.dtype)
        return mm(h, w2, group)

    def race(name, mm):
        loss = lambda *a: jnp.sum(experts(*a, mm).astype(jnp.float32) ** 2)
        row = {"impl": name, "rows": R, "d": d, "f": f, "held": G,
               "device": device}
        try:
            row["value_and_grad_us"] = timed(jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2))), x, w1, w2)[0]
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(row), flush=True)

    race("program", moe.grouped_matmul)
    race("ragged_dot", lambda a, b, g: lax.ragged_dot(
        a, b, g, preferred_element_type=jnp.float32,
        precision=lax.Precision.DEFAULT).astype(a.dtype))
    # a dense product of the same rows through ONE expert: the floor
    race("dense_one_expert", lambda a, b, g: jnp.matmul(
        a, b[0], preferred_element_type=jnp.float32).astype(a.dtype))


if __name__ == "__main__":
    main()
