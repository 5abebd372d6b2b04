"""Kernels: the least time the chip could take for the "conv" mixers of the
traced slice (LFM2's gated short convolution), over the device time of the
operations under `short_conv`, whatever implements them.

Required work of ONE layer's forward pass over N tokens (batch x sequence)
of width d with L taps: the input projection d -> 3 d and the output
projection d -> d, 2 N (3 d^2 + d^2) = 8 N d^2 FLOPs, and the taps, 2 L N d
(L multiply-adds a channel; the two gating products are elementwise and not
counted); the backward pass twice that (the rows' gradients and the
weights'); a recomputed forward is not required work. Bytes: the weights
once and the rows in and out once forward (4 d^2 + L d weights, 2 N d
numbers); backward the weights read and their gradients written, the rows,
and the cotangents of the output read, the rows' gradients written (2 (4 d^2
+ L d) + 3 N d). Least time = max(FLOPs / bfloat16 peak, bytes / HBM peak):
at N = 4 x 8,192, d 2,048, L 3, bfloat16 1.09991 TFLOP, 5,583.3 us, against
302.0 MB, 368.7 us, forward; 11,166.6 us of FLOPs backward: FLOPs bind. Calls
in the slice: the traffic's traced steps x the configuration's conv
layers."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "higher", "source": "device_trace"}


def conv_flops(n, d, taps, backward=False):
    return (2 if backward else 1) * (8 * n * d * d + 2 * taps * n * d)


def conv_bytes(n, d, taps, itemsize, backward=False):
    weights, rows = 4 * d * d + taps * d, n * d
    return itemsize * ((2 * weights + 3 * rows) if backward
                       else (weights + 2 * rows))


def least_seconds(n, d, taps, itemsize, peaks, backward=False):
    return max(conv_flops(n, d, taps, backward) / peaks["bf16_flops_per_s"],
               conv_bytes(n, d, taps, itemsize, backward)
               / peaks["hbm_bytes_per_s"])


def read(run):
    reduced = op_scopes.of(run)
    config, mix = run["cell"].config, run["cell"].traffic
    if not reduced or "conv_L_cache" not in config:
        return None
    seconds = sum(r["seconds"] for r in reduced["rows"]
                  if "short_conv" in r["words"])
    if not seconds:
        return None
    layers = config["layer_types"][:config["num_hidden_layers"]].count(
        "conv")
    sizes = (mix["batch_per_chip"] * run["cell"].chips * mix["seq_len"],
             config["hidden_size"], config["conv_L_cache"],
             {"bfloat16": 2, "float32": 4}[mix["dtype"]])
    forward = least_seconds(*sizes, run["peaks"])
    backward = least_seconds(*sizes, run["peaks"], backward=True)
    calls = mix["trace"]["steps"] * layers
    print(f"[short_conv_roofline] {calls} layer calls in the slice: least "
          f"{1e6 * forward:.1f} us forward, {1e6 * backward:.1f} us "
          f"backward; under `short_conv` {1e6 * seconds / calls:.1f} us a "
          f"layer call (forward, recomputed and backward)", flush=True)
    return 100.0 * calls * (forward + backward) / seconds
