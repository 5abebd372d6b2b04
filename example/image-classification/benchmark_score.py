#!/usr/bin/env python
"""Inference throughput over the model zoo (reference
example/image-classification/benchmark_score.py — the source of
BASELINE.md's inference rows).

Scans batch sizes per network; each measurement runs its loop on-device
(lax.scan with carry feedback) so per-dispatch host time doesn't pollute
the number.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def score(network, batch, steps, dtype):
    import jax
    import jax.numpy as jnp
    from jax import lax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.parallel.functional import functionalize

    net = getattr(vision, network)(classes=1000)
    net.initialize(mx.init.Xavier())
    if dtype != "float32":
        net.cast(dtype)
    size = 299 if "inception" in network else 224
    x0 = mx.nd.array(np.random.randn(batch, 3, size, size)
                     .astype(np.float32)).astype(dtype)
    params, apply_fn = functionalize(net, [x0], training=False)
    rng = jax.random.PRNGKey(0)
    xa = x0._data

    def loop(p, r, xx):
        def body(c, _):
            out = apply_fn(p, r, xx + c.astype(xx.dtype))[0][0]
            return out.astype(jnp.float32).mean() * 1e-12, None
        s, _ = lax.scan(body, jnp.float32(0), None, length=steps)
        return s

    fwd = jax.jit(loop)
    s = fwd(params, rng, xa)
    s.block_until_ready()
    np.asarray(s)
    t0 = time.perf_counter()
    s = fwd(params, rng, xa)
    s.block_until_ready()
    np.asarray(s)
    return batch * steps / (time.perf_counter() - t0)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--networks",
                   default="alexnet,vgg16,resnet50_v1,resnet152_v1,"
                           "inception_v3,mobilenet1_0,densenet121,"
                           "squeezenet1_0")
    p.add_argument("--batch-sizes", default="1,32,128")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    args = p.parse_args()
    for net in args.networks.split(","):
        for b in (int(x) for x in args.batch_sizes.split(",")):
            try:
                ips = score(net, b, args.steps, args.dtype)
            except Exception as e:
                print(f"network: {net}, batch {b}: FAILED {e!r}")
                continue
            print(f"network: {net}, batch size: {b}, dtype: {args.dtype}, "
                  f"images/sec: {ips:.2f}")


if __name__ == "__main__":
    main()
