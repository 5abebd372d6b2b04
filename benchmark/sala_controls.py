"""The controls of `minicpm-sala.train-8k`'s comparison with its reference:
the builder's tool, run by hand on the chip, never by the benchmark.

    python benchmark/sala_controls.py --seeds 1 2 3 [--seq 8192]

For each seed it draws the cell's weights, runs the step's own forward pass
(bfloat16, the flash kernels, the chunked scan) and prints the largest logit
error over the largest reference logit against the float32 reference, and
against the reference with each control of perfbench/reference/
minicpm_sala.py: the lightning decay dropped, rotary dropped, the mixers'
matrix products in float8. The family's TOLERANCE has to lie between the
first number and the least of the other three (PERF.md section 4).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--cell", default="minicpm-sala.train-8k")
    args = ap.parse_args()
    import jax
    from perfbench import cells
    from perfbench.families import minicpm_sala as family
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    cell = cells.resolve(args.cell)
    seq = args.seq or cell.traffic["seq_len"]
    model = TransformerLM(family.model_config(cell.config, cell.traffic))
    for seed in args.seeds:
        params = jax.jit(lambda k: family.draw_params(model, k))(
            jax.random.PRNGKey(seed))
        row = {"seed": seed, "seq": seq,
               "device": jax.devices()[0].device_kind,
               "tolerance": family.TOLERANCE}
        for drop in ((), ("decay",), ("rope",), ("precision",)):
            got = family.against_reference(model, params, cell.config, seed,
                                           1, seq, drop=drop)
            row["+".join(drop) or "system"] = got["relative_error"]
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
