"""How many rows `laguna-s-2.1.train-8k`'s expert buffer needs: the builder's
tool, run by hand on the chip, never by the benchmark.

    python benchmark/laguna_rows.py --seeds 1 2 3 [--steps 90 --rows 16384]

On one chip of an expert-parallel deployment the router is trained through
the held experts alone (no exchange: the other 248 return nothing, no
gradient), so at the cell's learning rate it drifts: a layer's held token
slots leave the uniform share within tens of steps, to either side. For
each seed this trains the cell's own step (its traffic file's lr, ring and
weights; `--rows` in place of its `expert_rows`, so that nothing is cut off
while counting) for `--steps` steps, more than a traced run's warm-up,
window and slice together, and prints a line of JSON: each expert layer's
fewest and most held slots over the steps and the step of the most, the
most of all, and the first losses (the warm-up band's). `expert_rows` is
sized from the most over the seeds (PERF.md section 6, PR 36)."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--steps", type=int, default=90)
    ap.add_argument("--rows", type=int, default=16384)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from perfbench import cells
    from perfbench.families import laguna as family
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    from incubator_mxnet_tpu.parallel import make_mesh
    cell = cells.resolve("laguna-s-2.1.train-8k")
    config = cell.config
    mix = dict(cell.traffic, expert_rows=args.rows)
    model = TransformerLM(family.model_config(config, mix))
    step, shard_params, init_opt = model.make_train_step(
        make_mesh({"dp": 1}, jax.devices()[:1]), lr=mix["lr"], use_sp=False)
    shape = (mix["ring"], mix["batch_per_chip"], mix["seq_len"])
    for seed in args.seeds:
        key = jax.random.PRNGKey(seed)
        params = shard_params(jax.jit(
            lambda k: family.draw_params(model, k))(key))
        opt = init_opt(params)
        ring = jax.random.randint(jax.random.fold_in(key, 1), shape, 0,
                                  config["vocab_size"], jnp.int32)
        losses, routed = [], []
        for i in range(args.steps):
            tokens = ring[i % mix["ring"]]
            params, opt, loss, counts = step(params, opt, tokens,
                                             jnp.roll(tokens, -1, 1), i)
            losses.append(loss)
            routed.append(counts["held_slots"])
        held = np.asarray(jax.device_get(routed))          # (steps, layers)
        losses = [float(x) for x in jax.device_get(losses)]
        del params, opt
        print(json.dumps({
            "seed": seed, "lr": mix["lr"], "steps": args.steps,
            "rows": args.rows, "device": jax.devices()[0].device_kind,
            "most": int(held.max()),
            "layers": {f"layer{layer}": {
                "first": int(held[0, j]), "fewest": int(held[:, j].min()),
                "most": int(held[:, j].max()),
                "most_at_step": int(held[:, j].argmax()),
                "last": int(held[-1, j])}
                for j, layer in enumerate(model.expert_layers)},
            "first_losses": [round(x, 4) for x in losses[:4]],
            "last_loss": round(losses[-1], 4),
            "finite": bool(np.isfinite(losses).all())}), flush=True)


if __name__ == "__main__":
    main()
