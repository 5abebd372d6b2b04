"""ResNet v1 (He et al., arXiv:1512.03385, Table 1), forward pass.

Follows the Gluon model zoo's `resnet*_v1`: post-activation units, the
bottleneck's stride on its first 1x1 convolution, projection shortcuts
(1x1 convolution + BatchNorm) where shape changes, no convolution biases.
Takes the weights in the order the layers are created: stem convolution
and BatchNorm, then per unit its convolution/BatchNorm pairs followed by
the shortcut's pair if it has one, then the classifier.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, w, stride):
    pad = w.shape[2] // 2
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)


def _bn(x, p, training, eps):
    gamma, beta, mean, var = (a.reshape(1, -1, 1, 1) for a in p)
    if training:                # the batch's own statistics, biased variance
        mean = jnp.mean(x, (0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(x - mean), (0, 2, 3), keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def forward(convs, bns, dense, x, cfg, training):
    """Logits (N, classes). `convs`: OIHW weights; `bns`: (gamma, beta,
    running_mean, running_var) tuples; `dense`: (weight (classes, C),
    bias); all in creation order. `training` normalizes with batch
    statistics, as a train step's forward pass does."""
    convs, bns = iter(convs), iter(bns)
    f32 = lambda t: jax.tree_util.tree_map(           # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    eps = cfg["bn_eps"]
    stem = cfg["stem"]
    x = f32(x)
    x = jax.nn.relu(_bn(_conv(x, f32(next(convs)), stem["stride"]),
                        f32(next(bns)), training, eps))
    if stem.get("maxpool"):
        k, s = stem["maxpool"]
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, k, k),
                              (1, 1, s, s),
                              [(0, 0), (0, 0), (k // 2, k // 2),
                               (k // 2, k // 2)])
    cin = stem["channels"]
    n_convs = 3 if cfg["unit"] == "bottleneck" else 2
    for stage, (width, units) in enumerate(zip(cfg["stage_widths"],
                                               cfg["stage_units"])):
        cout = width * cfg["expansion"]
        for unit in range(units):
            stride = 2 if unit == 0 and stage > 0 else 1
            y = x
            for i in range(n_convs):
                y = _bn(_conv(y, f32(next(convs)), stride if i == 0 else 1),
                        f32(next(bns)), training, eps)
                if i < n_convs - 1:
                    y = jax.nn.relu(y)
            if unit == 0 and (cin != cout or stride != 1):
                x = _bn(_conv(x, f32(next(convs)), stride), f32(next(bns)),
                        training, eps)
            x = jax.nn.relu(y + x)
            cin = cout
    x = jnp.mean(x, (2, 3))
    w, b = f32(dense)
    return jnp.dot(x, w.T, precision=lax.Precision.HIGHEST) + b
