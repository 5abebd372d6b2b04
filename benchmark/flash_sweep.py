"""Device time of the three flash kernels, causal, by sub-block edge.

    python benchmark/flash_sweep.py [--subs 0,128,256,512] [--calls 10]
        [--shapes 512x1024,256x2048,64x8192] [--out _chip/flash_sweep]

One line of JSON per (shape, edge): microseconds a call of `flash_fwd`,
`flash_bwd_dq` and `flash_bwd_dkv` at (BH, T, 64) bf16, read from a device
trace by kernel name (`perfbench/op_scopes.py`; host timing of a 2 ms kernel
is noise). Edge 0 leaves the module as it is, which is also all that a tree
from before PR 26 can run: unpack the parent beside this tree and run the
same file there for its column. Needs a TPU; exits 2 without one. The edge is
set on the module for the sweep only: it is no option of the program
(PERF.md section 6, PR 26, has the table this printed).
"""
import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def kernel_us(trace_dir, body):
    """{kernel name: microseconds a call} of the custom calls body() ran."""
    from perfbench import op_scopes, spans
    from perfbench.trace_reduce import find_xplane
    spans.traced_slice(trace_dir, body)
    out = {}
    for row in op_scopes.reduce(find_xplane(trace_dir))["rows"]:
        if row["category"] == "custom-call":
            out[row["op"].split()[1].split(".")[0]] = 1e6 * row["seconds"] / row["calls"]
    return out


def check(fa, jax, jnp, np, t):
    """The kernels against attention_reference on the device at length t:
    the worst error of output and gradients over the reference's largest."""
    from incubator_mxnet_tpu.parallel.ring_attention import \
        attention_reference
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((2, t, 2, 64)),
                           jnp.bfloat16) for _ in range(3))

    def both(attn):
        loss = lambda *a: attn(*a, causal=True).astype(jnp.float32).sum()
        return (attn(q, k, v, causal=True),) + \
            jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    worst = 0.0
    for got, want in zip(both(fa.flash_attention),
                         both(attention_reference)):
        want = np.asarray(want, np.float32)
        worst = max(worst, float(np.abs(np.asarray(got, np.float32) - want)
                                 .max() / max(1e-3, np.abs(want).max())))
    assert worst < 0.05, worst
    return worst


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--subs", default="0")
    ap.add_argument("--shapes", default="512x1024,256x2048,64x8192")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--out", default=os.path.join(ROOT, "_chip",
                                                  "flash_sweep"))
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.default_backend() != "tpu":
        print("flash_sweep: no TPU here; a CPU time is no kernel time",
              file=sys.stderr)
        return 2
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    for sub in map(int, args.subs.split(",")):
        if sub:
            fa._SUB = sub
        worst = max(check(fa, jax, jnp, np, t) for t in (1024, 2048))
        for shape in args.shapes.split(","):
            bh, t = map(int, shape.split("x"))
            block = fa._pick_block(t)
            keys = jax.random.split(jax.random.PRNGKey(t), 4)
            q, k, v, do = (jax.random.normal(key, (bh, t, 64), jnp.bfloat16)
                           for key in keys)
            fwd = jax.jit(lambda q, k, v: fa._fa_forward(
                q, k, v, True, 0.125, block, block, False))
            bwd = jax.jit(lambda q, k, v, do, lse, out: fa._fa_backward(
                q, k, v, do, lse, out, jnp.zeros_like(lse), True, 0.125,
                block, block, False))
            out, lse = fwd(q, k, v)
            jax.block_until_ready(bwd(q, k, v, do, lse, out))

            def body():
                for _ in range(args.calls):
                    res = fwd(q, k, v), bwd(q, k, v, do, lse, out)
                jax.block_until_ready(res)
            us = kernel_us(os.path.join(args.out, f"{shape}-{sub}"), body)
            print(json.dumps({"bh": bh, "t": t, "sub": sub, "block": block,
                              "check_rel_err": worst, "us_a_call": us}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
