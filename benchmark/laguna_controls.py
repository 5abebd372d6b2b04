"""The controls of `laguna-s-2.1.train-8k`'s comparison with its reference:
the builder's tool, run by hand on the chip, never by the benchmark.

    python benchmark/laguna_controls.py --seeds 1 2 3 [--seq 8192]

For each seed it draws the cell's weights, runs the step's own forward pass
(bfloat16, the flash kernels full and windowed, the grouped experts) and
prints a line of JSON: the family's own comparison (`family.readings`, whose
`ok` decides the cell's `correct`) on the system and on each control of
perfbench/reference/laguna.py (the window dropped, the gate dropped, the
routed experts dropped, YaRN dropped, the products in float8): the largest
logit error over the largest reference logit with the program's expert
choices handed to the reference (`relative_error`) and with the reference
routing by itself (`own_routing`), the token slots, of all and of those that
fall on a held expert, whose expert the two chose differently, and the
verdict; each expert layer's held slots and the most and fewest slots any of
the 256 experts drew. The system has to come out `ok`, every control not
`ok`, through that one function: `as_it_should` says whether they did, and
the exit code is 1 where a seed's did not. The family's limits have to lie
between the system's readings and the least control's (PERF.md section
4)."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--cell", default="laguna-s-2.1.train-8k")
    ap.add_argument("--controls", nargs="*", default=[
        "window", "gate", "experts", "yarn", "precision"])
    args = ap.parse_args()
    import jax
    import numpy as np
    from perfbench import cells
    from perfbench.families import laguna as family
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    cell = cells.resolve(args.cell)
    config = cell.config
    seq = args.seq or cell.traffic["seq_len"]
    model = TransformerLM(family.model_config(config, cell.traffic))
    first, count = model.cfg.experts.held
    wrong = 0
    for seed in args.seeds:
        params = jax.jit(lambda k: family.draw_params(model, k))(
            jax.random.PRNGKey(seed))
        tokens = family.sample(config, seed, 1, seq)
        system = family.system_forward(model, params, tokens)
        row = {"seed": seed, "seq": seq,
               "device": jax.devices()[0].device_kind,
               "system": family.readings(model, config, params, tokens,
                                         system)}
        for layer, chosen in sorted(system[1].items()):
            drew = np.bincount(np.asarray(chosen).reshape(-1),
                               minlength=config["published"]["num_experts"])
            row[f"layer{layer}"] = {
                "held_slots": int(drew[first:first + count].sum()),
                "most": int(drew.max()), "fewest": int(drew.min())}
        for drop in args.controls:
            row[drop] = family.readings(model, config, params, tokens,
                                        system, drop=(drop,))
        row["as_it_should"] = row["system"]["ok"] and not any(
            row[drop]["ok"] for drop in args.controls)
        wrong += not row["as_it_should"]
        print(json.dumps(row), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
