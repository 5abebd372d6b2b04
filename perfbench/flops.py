"""The floating-point operations a model's forward and backward passes
REQUIRE per item, computed from the configuration's sizes, and the table
of the chip's published peaks. A multiply-add is two operations, as in the
peaks. Recomputation (remat) is not required work and is not counted.
"""
from __future__ import annotations

import os

from .cells import HERE, BenchError, load_json


def device_peaks(device_kind):
    """The row of peaks.json for this exact device_kind. A device that is
    not in the table is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind == "source":
        raise BenchError(
            f"no published peaks for device kind {device_kind!r} in "
            f"perfbench/peaks.json (known: "
            f"{sorted(k for k in table if k != 'source')})")
    return table[device_kind]


def resnet_forward_macs(cfg):
    """Multiply-adds of one image's forward pass through the convolutions
    and the classifier of a ResNet v1 as the configuration describes it
    (BatchNorm, ReLU, pooling and the residual adds are bandwidth, not
    counted). ResNet-50 v1, stride on the first 1x1: 3.86e9."""
    _, h, w = cfg["image_shape"]
    stem = cfg["stem"]

    def out(n, k, s):                   # 'same'-style padding k // 2
        return (n + 2 * (k // 2) - k) // s + 1
    h, w = out(h, stem["kernel"], stem["stride"]), \
        out(w, stem["kernel"], stem["stride"])
    macs = stem["kernel"] ** 2 * cfg["image_shape"][0] * stem["channels"] \
        * h * w
    if stem.get("maxpool"):
        k, s = stem["maxpool"]
        h, w = out(h, k, s), out(w, k, s)
    cin = stem["channels"]
    bottleneck = cfg["unit"] == "bottleneck"
    for stage, (width, units) in enumerate(zip(cfg["stage_widths"],
                                               cfg["stage_units"])):
        cout = width * cfg["expansion"]
        for unit in range(units):
            stride = 2 if unit == 0 and stage > 0 else 1
            project = unit == 0 and (cin != cout or stride != 1)
            ho, wo = out(h, 1, stride), out(w, 1, stride)
            if bottleneck:      # 1x1 (strided) -> 3x3 -> 1x1
                macs += (cin * width + 9 * width * width + width * cout) \
                    * ho * wo
            else:               # 3x3 (strided) -> 3x3
                macs += (9 * cin * width + 9 * width * cout) * ho * wo
            if project:
                macs += cin * cout * ho * wo
            h, w, cin = ho, wo, cout
    return macs + cin * cfg["classes"]


def resnet_train_flops_per_item(cfg):
    """Forward once, backward twice (gradients of inputs and of weights)."""
    return 3 * 2 * resnet_forward_macs(cfg)


def transformer_matmul_params(cfg):
    """Parameters that a token multiplies: the blocks' six matrices and the
    (tied) output projection. Embedding look-ups and LayerNorms are not
    matrix multiplications."""
    d, f = cfg["d_model"], cfg["d_ff"]
    return cfg["n_layers"] * (4 * d * d + 2 * d * f) + cfg["vocab_size"] * d


def transformer_train_flops_per_item(cfg, seq_len):
    """Per token: 6 N for the matrix multiplications, plus causal
    attention. A token attends to T/2 keys on average, so QK^T and AV cost
    2 * 2 * (T/2) * d forward per layer and three times that with the
    backward pass: 6 T d per layer. (bench.py's 12 L T d counts the masked
    half as well.)"""
    attn = 6 * cfg["n_layers"] * seq_len * cfg["d_model"]
    return 6 * transformer_matmul_params(cfg) + attn
