"""Kernels: the least time the chip could take for the decayed
linear-attention calls of the traced slice, over the device time of the
operations under `lightning`.

Required work of ONE layer's forward call over q, k, v, o (B, T, H, d) is
the recurrence's, whatever chunking implements it: a state update k v^T and
a read-out q S, 2 H d^2 multiply-adds a token, 4 B T H d^2 FLOPs; the
backward call twice that (dq through the state, dk and dv through its
mirror image). Bytes: the forward reads q, k, v and writes o; the backward
reads q, k, v, dO and writes dq, dk, dv (o is no operand of it). Least
time = max(FLOPs / bfloat16 peak, bytes / HBM peak): at (1, 8192, 32, 128)
bfloat16 87 us against 328 us forward, 175 against 573 backward, so HBM
binds. Calls in the slice: the traffic's traced steps x the
configuration's lightning layers x (forward, once more where the block is
recomputed, and backward)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "higher", "source": "device_trace"}


def lightning_flops(b, t, h, d, backward=False):
    return (2 if backward else 1) * 4 * b * t * h * d * d


def lightning_bytes(b, t, h, d, itemsize, backward=False):
    return (7 if backward else 4) * b * t * h * d * itemsize


def least_seconds(b, t, h, d, itemsize, peaks, backward=False):
    return max(lightning_flops(b, t, h, d, backward)
               / peaks["bf16_flops_per_s"],
               lightning_bytes(b, t, h, d, itemsize, backward)
               / peaks["hbm_bytes_per_s"])


def read(run):
    reduced = op_scopes.of(run)
    config, mix = run["cell"].config, run["cell"].traffic
    if not reduced or "lightning_nh" not in config:
        return None
    seconds = sum(r["seconds"] for r in reduced["rows"]
                  if "lightning" in r["words"])
    if not seconds:
        return None
    layers = config["mixer_types"][:config["num_hidden_layers"]].count(
        "lightning-attn")
    shape = (mix["batch_per_chip"], mix["seq_len"], config["lightning_nh"],
             config["lightning_head_dim"],
             {"bfloat16": 2, "float32": 4}[mix["dtype"]])
    forward = least_seconds(*shape, run["peaks"])
    backward = least_seconds(*shape, run["peaks"], backward=True)
    calls = mix["trace"]["steps"] * layers
    least = calls * ((2 if mix["remat"] else 1) * forward + backward)
    print(f"[lightning_roofline] {calls} layer calls in the slice: least "
          f"{1e6 * forward:.1f} us forward, {1e6 * backward:.1f} us "
          f"backward; under `lightning` {1e6 * seconds / calls:.1f} us a "
          f"layer call (forward, recomputed and backward)", flush=True)
    return 100.0 * least / seconds
