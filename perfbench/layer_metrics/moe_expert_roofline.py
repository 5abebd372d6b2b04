"""Kernels: the least time the chip could take for the held experts' grouped
products of the traced slice, over the device time of the operations under
`moe_experts`, whatever implements them.

Required work of ONE expert layer's forward pass over S held token slots
(the step's own counter `moe.held_slots`, a layer's mean; the buffer's spare
rows are no required work): three products a slot, gate and up (d x f each)
and down (f x d): 6 S d f FLOPs; the backward pass twice that (the gradients
of the rows and of the weights); a recomputed forward is not required work.
Bytes: the held experts' weights once and the rows in and out once forward
(E 3 d f + 2 S d numbers); backward the weights read and their gradients
written, the rows and their cotangents read, the rows' gradients written
(2 E 3 d f + 4 S d). Least time = max(FLOPs / bfloat16 peak, bytes / HBM
peak): at S 2,560, d 3,072, f 1,024, E 8 bfloat16 245 us of FLOPs against
230 us of bytes forward, 491 against 446 backward. Calls in the slice: the
traffic's traced steps x the configuration's expert layers."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "higher", "source": "device_trace"}


def expert_flops(slots, d, f, backward=False):
    return (2 if backward else 1) * 6 * slots * d * f


def expert_bytes(slots, d, f, held, itemsize, backward=False):
    weights, rows = held * 3 * d * f, slots * d
    return itemsize * ((2 * weights + 4 * rows) if backward
                       else (weights + 2 * rows))


def least_seconds(slots, d, f, held, itemsize, peaks, backward=False):
    return max(expert_flops(slots, d, f, backward)
               / peaks["bf16_flops_per_s"],
               expert_bytes(slots, d, f, held, itemsize, backward)
               / peaks["hbm_bytes_per_s"])


def read(run):
    reduced = op_scopes.of(run)
    config, mix = run["cell"].config, run["cell"].traffic
    counters = run.get("counters")
    if not reduced or "moe_intermediate_size" not in config or not counters:
        return None
    slots = counters.over("moe.held_slots", "at_end")
    seconds = sum(r["seconds"] for r in reduced["rows"]
                  if "moe_experts" in r["words"])
    if not seconds or not slots:
        return None
    layers = config["mlp_layer_types"][:config["num_hidden_layers"]].count(
        "sparse")
    sizes = (slots, config["hidden_size"], config["moe_intermediate_size"],
             config["num_experts"],
             {"bfloat16": 2, "float32": 4}[mix["dtype"]])
    forward = least_seconds(*sizes, run["peaks"])
    backward = least_seconds(*sizes, run["peaks"], backward=True)
    calls = mix["trace"]["steps"] * layers
    print(f"[moe_expert_roofline] {calls} layer calls in the slice of "
          f"{slots:.1f} held slots: least {1e6 * forward:.1f} us forward, "
          f"{1e6 * backward:.1f} us backward; under `moe_experts` "
          f"{1e6 * seconds / calls:.1f} us a layer call (forward, recomputed "
          f"and backward)", flush=True)
    return 100.0 * calls * (forward + backward) / seconds
