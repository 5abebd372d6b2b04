"""The race for the expert layer's grouped products: the builder's tool,
run by hand on the chip, never by the benchmark.

    python benchmark/moe_race.py [--rows 4096 --d 3072 --f 1024 --held 8]

Times value and gradient of the routed part's two grouped products (gate and
up as one, then down) over `rows` sorted rows, with the groups near uniform
as a seeded router leaves them and the spare rows with the last: the
program's own (`parallel/moe.grouped_matmul`: jax's `megablox` kernels under
the program's custom_vjp and tiling), those kernels at other tilings,
`lax.ragged_dot`, and the same rows through one expert (the floor). Each
row also CHECKS what it timed: the worst error of the result and of the
three gradients, over the largest value of each, against a float32 loop
over the experts at `highest` on the same bfloat16 operands (`check_rel_err`:
what Mosaic compiled, not the interpreter). PERF.md section 6, PR 36, has
the readings."""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


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
    R, d, f, G = args.rows, args.d, args.f, args.held
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (R, d), jnp.bfloat16)
    w1 = (jax.random.normal(ks[1], (G, d, 2 * f)) / d ** 0.5).astype(
        jnp.bfloat16)
    w2 = (jax.random.normal(ks[2], (G, f, d)) / f ** 0.5).astype(jnp.bfloat16)
    sizes = np.random.default_rng(0).multinomial(R * 5 // 8, [1 / G] * G)
    sizes[-1] += R - sizes.sum()
    group = jnp.asarray(sizes, jnp.int32)

    def experts(x, w1, w2, mm):
        h = mm(x, w1, group)
        h = (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(x.dtype)
        return mm(h, w2, group)

    def loop(a, b, _):
        """Float32, an expert at a time, no grouped primitive."""
        of = jnp.asarray(np.repeat(np.arange(G), sizes))[:, None]
        a = a.astype(jnp.float32)
        return sum(jnp.where(of == g, jnp.matmul(
            a, b[g].astype(jnp.float32), precision=lax.Precision.HIGHEST), 0)
            for g in range(G))

    def both(mm):
        loss = lambda *a: jnp.sum(experts(*a, mm).astype(jnp.float32) ** 2)
        return (jax.jit(lambda *a: experts(*a, mm)),
                jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))))

    want = [both(loop)[0](x, w1, w2), *both(loop)[1](x, w1, w2)[1]]

    def race(name, mm, check=True):
        fwd, fn = both(mm)
        row = {"impl": name, "rows": R, "d": d, "f": f, "held": G,
               "device": jax.devices()[0].device_kind}
        try:
            for what, g in (("forward_us", fwd), ("value_and_grad_us", fn)):
                jax.block_until_ready(g(x, w1, w2))
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    out = g(x, w1, w2)
                jax.block_until_ready(out)
                row[what] = 1e6 * (time.perf_counter() - t0) / args.reps
            if check:
                got = [fwd(x, w1, w2), *fn(x, w1, w2)[1]]
                row["check_rel_err"] = {
                    k: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                             / jnp.max(jnp.abs(w)))
                    for k, g, w in zip(("out", "d_x", "d_gate_in", "d_out"),
                                       got, want)}
        except Exception as e:          # a tiling the chip refuses: say so
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(row), flush=True)

    race("program", moe.grouped_matmul)
    # wider tiles than these ran out of the chip's scoped memory (PR 36)
    for tiling in ((128, 1024, 1024), (512, 1024, 1024), (256, 512, 1024)):
        race("megablox" + str(tiling), lambda a, b, g, t=tiling:
             moe._megablox(a, b, g, t))
    race("ragged_dot", lambda a, b, g: lax.ragged_dot(
        a, b, g, preferred_element_type=jnp.float32,
        precision=lax.Precision.DEFAULT).astype(a.dtype))
    # a dense product of the same rows through ONE expert: the floor
    race("dense_one_expert", lambda a, b, g: jnp.matmul(
        a, b[0], preferred_element_type=jnp.float32).astype(a.dtype),
        check=False)


if __name__ == "__main__":
    main()
