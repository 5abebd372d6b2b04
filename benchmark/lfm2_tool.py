"""The measurements behind `lfm2-8b-a1b.train-4x8k-moe`'s comparison limits
and expert buffer, run by hand on the chip, never by the benchmark.

    python benchmark/lfm2_tool.py controls --seeds 1 2 3 [--seq 8192]
    python benchmark/lfm2_tool.py rows --seeds 1 2 3 [--steps 80 --rows R]
    python benchmark/lfm2_tool.py band --seeds 1 2 3

`controls`: for each seed it draws the cell's weights, runs the step's own
forward pass (bfloat16, the conv mixers, the flash kernels, the grouped
experts) on the cell's comparison sample (its traffic file's `check_items`
sequences, the timed batch) and prints a line of JSON: the family's own
comparison (`family.readings`, whose `ok` decides the cell's `correct`) on
the system and on each control of perfbench/reference/lfm2_moe.py (the taps
dropped, the taps reversed, QK-norm dropped, the expert bias dropped, the
products in float8). The system has to come out `ok`, every control not
`ok`: `as_it_should` says whether they did, and the exit code is 1 where a
seed's did not. The bias control's `slots_differing` is the share of the
routing choices that the drawn bias changes against a bias of zeros.

`rows`: for each seed it trains the cell's own step (its traffic file's lr,
batch, ring and weights; `--rows` in place of its `expert_rows`, so that
nothing is cut off while counting) for `--steps` steps, more than a traced
run's warm-up, window and slice together, and prints a line of JSON: each
expert layer's fewest and most held slots over the steps, the most of all,
the most-loaded expert over the mean (first and last step, a layer's mean),
and the first losses. `expert_rows` is sized from the most over the seeds
(PERF.md section 4).

`band`: for each seed, the cell's own warm-up (its step, weights, ring and
`warmup_steps`) and the losses it reads, beside two controls at the band's
step, the ring's first batch seen again: `frozen`, a step that changes
nothing, reads the drawn weights' loss on that batch, which is warm-up step
0's; `part` is the loss there after steps that trained on one sequence of
each batch (its first, four times). The band is set from the seeds'
readings, clear of both controls."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "lfm2-8b-a1b.train-4x8k-moe"
CONTROLS = ("taps", "reversed", "qk_norm", "bias", "precision")


def controls(args, cell, family, model):
    import jax
    wrong = 0
    seq = args.seq or cell.traffic["seq_len"]
    for seed in args.seeds:
        params = jax.jit(lambda k: family.draw_params(model, k))(
            jax.random.PRNGKey(seed))
        tokens = family.sample(cell.config, seed,
                               cell.traffic["check_items"], seq)
        system = family.system_forward(model, params, tokens)
        row = {"seed": seed, "seq": seq,
               "device": jax.devices()[0].device_kind,
               "system": family.readings(model, cell.config, params, tokens,
                                         system)}
        for drop in CONTROLS:
            row[drop] = family.readings(model, cell.config, params, tokens,
                                        system, drop=(drop,))
        row["as_it_should"] = row["system"]["ok"] and not any(
            row[drop]["ok"] for drop in CONTROLS)
        wrong += not row["as_it_should"]
        print(json.dumps(row), flush=True)
    return 1 if wrong else 0


def rows(args, cell, family, model):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from incubator_mxnet_tpu.parallel import make_mesh
    mix = cell.traffic
    step, shard_params, init_opt = model.make_train_step(
        make_mesh({"dp": 1}, jax.devices()[:1]), lr=mix["lr"], use_sp=False)
    shape = (mix["ring"], mix["batch_per_chip"], mix["seq_len"])
    for seed in args.seeds:
        key = jax.random.PRNGKey(seed)
        params = shard_params(jax.jit(
            lambda k: family.draw_params(model, k))(key))
        opt = init_opt(params)
        ring = jax.random.randint(jax.random.fold_in(key, 1), shape, 0,
                                  cell.config["vocab_size"], jnp.int32)
        losses, routed = [], []
        for i in range(args.steps):
            tokens = ring[i % mix["ring"]]
            params, opt, loss, counts = step(params, opt, tokens,
                                             jnp.roll(tokens, -1, 1), i)
            losses.append(loss)
            routed.append(counts)
        routed = jax.device_get(routed)
        held = np.asarray([r["held_slots"] for r in routed])   # (steps, L)
        load = np.asarray([r["load"] for r in routed], np.float64)
        ratio = (load.max(2) / load.mean(2)).mean(1)            # (steps,)
        losses = [float(x) for x in jax.device_get(losses)]
        print(json.dumps({
            "seed": seed, "steps": args.steps, "rows": args.rows,
            "fewest": held.min(0).tolist(), "most": held.max(0).tolist(),
            "most_at": held.argmax(0).tolist(), "most_of_all": int(held.max()),
            "mean_held": float(held.mean()),
            "load_max_over_mean": [float(ratio[0]), float(ratio[-1]),
                                   float(ratio.mean())],
            "over": int(np.sum([r["slots_over"] for r in routed])),
            "first_losses": losses[:6], "last_loss": losses[-1]}),
            flush=True)
    return 0


def band(args, cell, family, model):
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import make_mesh
    mix = cell.traffic
    at, ring_n = mix["warmup_loss_band"]["step"], mix["ring"]
    if at < ring_n or at % ring_n:
        raise SystemExit(f"band step {at}: not the ring's first batch again")
    step, shard_params, init_opt = model.make_train_step(
        make_mesh({"dp": 1}, jax.devices()[:1]), lr=mix["lr"], use_sp=False)
    shape = (ring_n, mix["batch_per_chip"], mix["seq_len"])

    def losses(key, batches):
        params = shard_params(jax.jit(
            lambda k: family.draw_params(model, k))(key))
        opt = init_opt(params)
        out = []
        for i, tokens in enumerate(batches):
            params, opt, loss, _ = step(params, opt, tokens,
                                        jnp.roll(tokens, -1, 1), i)
            out.append(loss)
        return [float(x) for x in jax.device_get(out)]

    for seed in args.seeds:
        key = jax.random.PRNGKey(seed)
        ring = jax.random.randint(jax.random.fold_in(key, 1), shape, 0,
                                  cell.config["vocab_size"], jnp.int32)
        warm = losses(key, [ring[i % ring_n]
                            for i in range(mix["warmup_steps"])])
        one = [jnp.broadcast_to(ring[i % ring_n][:1], shape[1:])
               for i in range(at)]
        part = losses(key, one + [ring[0]])[-1]
        print(json.dumps({"seed": seed, "warm": warm, "at": at,
                          "frozen": warm[0], "part": part}), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("controls", "rows", "band"))
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--rows", type=int, default=131072)
    args = ap.parse_args()
    from perfbench import cells
    from perfbench.families import lfm2_moe as family
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    cell = cells.resolve(CELL)
    mix = dict(cell.traffic)
    if args.what == "rows":
        mix["expert_rows"] = args.rows
    model = TransformerLM(family.model_config(cell.config, mix))
    return {"controls": controls, "rows": rows, "band": band}[args.what](
        args, cell, family, model)


if __name__ == "__main__":
    sys.exit(main())
