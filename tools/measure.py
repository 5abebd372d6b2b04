#!/usr/bin/env python
"""KVStore allreduce bandwidth harness.

Reference: tools/bandwidth/measure.py — times push+pull of ResNet-sized
gradient arrays through the kvstore and reports GB/s per round. Here the
comm path is mesh collectives (psum over ICI on TPU, virtual CPU mesh in
tests), so the number reported is the achieved allreduce bandwidth of
`kvstore.pushpull` end to end.

Usage:
  python tools/measure.py [--network resnet50] [--kv-store device]
                          [--rounds 10] [--devices 8]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# layer-gradient size profiles (num arrays x elements), roughly matching the
# reference's --network presets (parameter tensors of each model)
NETWORKS = {
    "alexnet": [(1, 37748736), (1, 16777216), (1, 4096 * 4096), (5, 1 << 20)],
    "resnet50": [(1, 2048 * 1000), (16, 1 << 21), (32, 1 << 19),
                 (53, 1 << 16)],
    "vgg16": [(1, 102760448), (2, 16777216), (13, 1 << 20)],
    "inception-v3": [(1, 2048 * 1000), (40, 1 << 18), (53, 1 << 16)],
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", default="resnet50", choices=sorted(NETWORKS))
    ap.add_argument("--kv-store", default="device",
                    choices=["local", "device", "tpu"])
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--devices", type=int, default=0,
                    help="force a virtual CPU mesh of this many devices")
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count="
                                   f"{args.devices}").strip()
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import numpy as np
    import incubator_mxnet_tpu as mx

    n_dev = len(jax.devices())
    kv = mx.kv.create(args.kv_store)
    shapes = NETWORKS[args.network]
    keys, sizes = [], []
    k = 0
    for count, elems in shapes:
        for _ in range(count):
            keys.append(str(k))
            sizes.append(elems)
            k += 1
    total_bytes = sum(sizes) * np.dtype(args.dtype).itemsize
    print(f"[measure] {args.network}: {len(keys)} arrays, "
          f"{total_bytes / 1e9:.3f} GB per round, {n_dev} devices, "
          f"kvstore={args.kv_store}", file=sys.stderr)

    vals = {}
    for key, n in zip(keys, sizes):
        arr = mx.nd.array(np.random.uniform(-1, 1, n).astype(args.dtype))
        kv.init(key, arr)
        vals[key] = arr

    outs = {key: mx.nd.zeros((n,), dtype=args.dtype)
            for key, n in zip(keys, sizes)}

    def round_trip():
        for key in keys:
            kv.push(key, vals[key])
        for key in keys:
            kv.pull(key, out=outs[key])
        for o in outs.values():
            o.wait_to_read()

    round_trip()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(args.rounds):
        round_trip()
    dt = time.perf_counter() - t0

    per_round = dt / args.rounds
    gbps = total_bytes / per_round / 1e9
    print(f"[measure] {per_round * 1e3:.2f} ms/round  "
          f"{gbps:.2f} GB/s effective", file=sys.stderr)
    import json
    print(json.dumps({"metric": f"kvstore_{args.kv_store}_bandwidth",
                      "network": args.network, "value": round(gbps, 3),
                      "unit": "GB/s", "ms_per_round": round(per_round * 1e3, 2),
                      "devices": n_dev,
                      "platform": jax.devices()[0].platform,
                      "device_kind": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
