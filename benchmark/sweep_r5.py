#!/usr/bin/env python
"""Round-5 on-chip measurement sweep.

One process, one backend init, all the round-5 perf experiments in
dependency order (cheapest signal first):

  1. ResNet train b128 bf16 — did the one-pass BN stat + scale/bias
     epilogue recomposition move the 15.7%-MFU row? (VERDICT item 2)
  2. Transformer remat-policy sweep at the flagship shape — is any
     selective-save policy >=5% tok/s over full remat? (item 3)
  3. fp32 fast-matmul mode — does MXTPU_FP32_MATMUL=fast lift the
     b32 fp32 train headline toward >=1,800 img/s? (item 4)

Prints one line per measurement; paste the table into
docs/perf_notes.md. The full BENCH_r05 capture stays bench.py's job.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np


def _sync(x):
    import bench
    bench._sync(x)


def resnet_train(batch, dtype, steps):
    import bench
    return bench.bench_train(batch, dtype, steps)


def transformer_policy(policy, steps=20):
    import jax
    import jax.numpy as jnp
    import bench
    from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                        TransformerLM)
    from incubator_mxnet_tpu.parallel import make_mesh

    sys.setrecursionlimit(20000)
    B, T, L, D = 32, 2048, 12, 1024
    cfg = TransformerConfig(vocab_size=32000, d_model=D, n_heads=16,
                            n_layers=L, d_ff=4 * D, max_len=T,
                            dtype="bfloat16", remat=True,
                            remat_policy=policy)
    model = TransformerLM(cfg)
    mesh = make_mesh({"dp": 1})
    step, shard_params, init_opt = model.make_train_step(
        mesh, lr=1e-3, use_sp=False, n_steps=steps)
    params = shard_params(model.init_params(jax.random.PRNGKey(0)))
    opt = init_opt(params)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T))
                         .astype(np.int32))
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, 1))
    params, opt, loss = step(params, opt, tokens, targets, 0)
    _sync(loss)
    params, opt, loss = step(params, opt, tokens, targets, steps)
    _sync(loss)   # second warmup at the REAL n (first-dispatch artifact)

    def run():
        nonlocal params, opt
        params, opt, loss = step(params, opt, tokens, targets, steps)
        _sync(loss)
    dt = bench._time_best(run)
    return B * T * steps / dt


def main():
    import bench
    dev = bench._require_tpu()      # no chip: exit, never a CPU number
    print(f"[sweep] device: {dev.device_kind}", flush=True)

    # 1. BN one-pass effect on the ResNet train rows
    for batch, dtype, steps in ((128, "bfloat16", 240), (32, "bfloat16", 240)):
        ips = resnet_train(batch, dtype, steps)
        print(f"[sweep] resnet train b{batch} {dtype}: {ips:9.1f} img/s "
              f"(r4 b128 ref 2520, b32 ref 2432)", flush=True)

    # 2. remat-policy sweep (flagship shape)
    for policy in (None, "save_mlp", "save_attn", "save_attn_mlp", "dots"):
        try:
            tok = transformer_policy(policy)
            print(f"[sweep] transformer remat_policy={policy!r}: "
                  f"{tok:9.0f} tok/s (r4 ref ~60.3k)", flush=True)
        except Exception as e:
            print(f"[sweep] transformer remat_policy={policy!r}: "
                  f"FAILED {type(e).__name__}: {str(e)[:200]}", flush=True)

    # 3. fp32 fast-mode headline
    from incubator_mxnet_tpu import runtime
    for mode, steps in (("strict", 60), ("fast", 60)):
        runtime.set_fp32_matmul_mode(mode)
        try:
            ips = resnet_train(32, "float32", steps)
            print(f"[sweep] resnet train b32 fp32 [{mode}]: {ips:9.1f} img/s "
                  f"(r4 strict ref 597; target fast >=1800)", flush=True)
        finally:
            runtime.set_fp32_matmul_mode("strict")


if __name__ == "__main__":
    main()
