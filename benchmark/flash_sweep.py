"""Device time of the flash kernels, causal, by sub-block edge.

    python benchmark/flash_sweep.py [--subs 0,128,256,512] [--calls 10]
        [--shapes 32x16x1024,16x16x2048,4x16x8192,1x32x8192x128,1x72x8192x128w512]
        [--out _chip/flash_sweep]

One line of JSON per (shape B x H x T, or B x H x T x D, `w` and a window
after it for a windowed call, whose kernels are `flash_win_*`; edge): under
`us_a_call`, microseconds a call of each kernel the trace holds, by name, on
(B, T, H, D) bf16, D = 64 unless given, the layout the model hands
`flash_attention`; `bwd_us`, their sum over the backward kernels, a layer's
backward whatever it is made of; and `other_us`: what else the device ran for
one forward and one backward call, which is the copies that stand round the
kernels (PR 33 took them away for shapes that pack two heads to a 128-lane
block) and, before PR 35, the array of zeros in the place of lse's cotangent.
Which kernels a shape's backward is (`dispatch` has the counts): ONE call
named `flash_bwd_dq` (`flash_win_bwd_dq` with a window) that emits dq, dk and
dv, where a call is one grid block in q and in k (T <= 1,024: PR 37) and, since
PR 39, at every causal length whose dq fits the chip's VMEM, 2,048 and 8,192
among them, plain or windowed; the pair `flash_bwd_dq`, `flash_bwd_dkv` on a
tree before that, as at every shape before PR 37: a parent's column has two
rows where the change's has one, and `bwd_us` is what to read side by side.
`pair_rel_err`, on a tree that keeps the pair as a function of its own
(`_fa_backward_pair`, PR 39): the worst difference of the one call's dq, dk, dv
from the pair's on the same operands, over the pair's largest. All read from a
device trace by kernel name (`perfbench/op_scopes.py`; host timing of a 2 ms
kernel is noise). Edge 0 leaves the module as it is. That comparison apart,
the file calls nothing but the public entry, so an older tree runs it too: unpack the parent beside
this tree, copy this file over its own, and run it there for the parent's
column. Needs a TPU; exits 2 without one. The edge is set on the module for
the sweep only: it is no option of the program (PERF.md section 6, PR 26, PR
33, PR 35, PR 37 and PR 39, has the tables this printed).
"""
import argparse
import functools
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def kernel_us(trace_dir, body, rounds):
    """({kernel name: microseconds a call} of the custom calls body() ran,
    microseconds a round of everything else it ran)."""
    from perfbench import op_scopes, spans
    from perfbench.trace_reduce import find_xplane
    spans.traced_slice(trace_dir, body)
    out, other = {}, 0.0
    for row in op_scopes.reduce(find_xplane(trace_dir))["rows"]:
        if row["category"] == "custom-call":
            out[row["op"].split()[1].split(".")[0]] = \
                1e6 * row["seconds"] / row["calls"]
        else:
            other += 1e6 * row["seconds"] / rounds
    return out, other


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


def against_pair(fa, jax, np, q, k, v, do, window):
    """The worst difference of _fa_backward's dq, dk, dv from the pair's on
    the kernels' own operands, over the pair's largest; None on a tree whose
    backward is the pair, or where the call takes it."""
    if not hasattr(fa, "_fa_backward_pair"):
        return None
    (B, T, H, D), direct = q.shape, fa._direct(*q.shape[2:])
    ops = [fa._operand(x, direct) for x in (q, k, v, do)]
    block = fa._pick_block(T)
    static = (D, True, D ** -0.5, block, block, False, window)
    out, lse = jax.jit(lambda *a: fa._fa_forward(*a, *static))(*ops[:3])
    before = fa.dispatch_stats()["bwd_fused"]
    one = jax.jit(lambda *a: fa._fa_backward(*a, None, *static))(
        *ops, lse, out)
    if fa.dispatch_stats()["bwd_fused"] == before:
        return None
    pair = jax.jit(lambda *a: fa._fa_backward_pair(*a, None, *static))(
        *ops, lse, out)
    worst = 0.0
    for a, b in zip(one, pair):
        b = np.asarray(b, np.float32)
        worst = max(worst, float(np.abs(np.asarray(a, np.float32) - b).max()
                                 / max(1e-3, np.abs(b).max())))
    return worst


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--subs", default="0")
    ap.add_argument("--shapes", default="32x16x1024,16x16x2048,4x16x8192,"
                                        "1x32x8192x128")
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
            dims, _, window = shape.partition("w")
            b, h, t, d = (tuple(map(int, dims.split("x"))) + (64,))[:4]
            keys = jax.random.split(jax.random.PRNGKey(t), 4)
            q, k, v, do = (jax.random.normal(key, (b, t, h, d),
                                             jnp.bfloat16) for key in keys)
            attn = functools.partial(
                fa.flash_attention, causal=True,
                **({"window": int(window)} if window else {}))
            # one forward call, and one backward call from its residuals
            fwd = jax.jit(lambda q, k, v: jax.vjp(attn, q, k, v))
            bwd = jax.jit(lambda pull, do: pull(do))
            pull = fwd(q, k, v)[1]
            jax.block_until_ready(bwd(pull, do))

            def body():
                for _ in range(args.calls):
                    res = bwd(fwd(q, k, v)[1], do)
                jax.block_until_ready(res)
            us, other = kernel_us(os.path.join(args.out, f"{shape}-{sub}"),
                                  body, args.calls)
            print(json.dumps({"b": b, "h": h, "t": t, "d": d, "sub": sub,
                              "window": int(window) if window else None,
                              "block": fa._pick_block(t),
                              "check_rel_err": worst,
                              "pair_rel_err": against_pair(
                                  fa, jax, np, q, k, v, do,
                                  int(window) if window else None),
                              "us_a_call": us,
                              "bwd_us": sum(v for name, v in us.items()
                                            if "_bwd_" in name),
                              "other_us": other,
                              "dispatch": fa.dispatch_stats()}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
