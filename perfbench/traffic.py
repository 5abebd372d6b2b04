"""The one traffic generator: everything a run feeds the program is drawn
here from `--seed` and the parameters of the cell's traffic file.

A traffic file (`traffic/<mix>.json`) is parameters only, so a later PR
adds a mix without adding code. Keys every mix has: `driver` (the loop in
drivers/ that plays it) and `why`. See README.md for each driver's keys.
"""
from __future__ import annotations

import numpy as np


def arrival_offsets(arrivals, seconds, seed):
    """Seconds from the window's start at which each request is due: a
    renewal process at `rate_per_s` whose gaps are gamma distributed with
    coefficient of variation `cv` (1 = Poisson; above 1 = bursts)."""
    rate = float(arrivals["rate_per_s"])
    cv = float(arrivals.get("cv", 1.0))
    if rate <= 0 or cv <= 0:
        raise ValueError(f"arrivals need rate_per_s > 0 and cv > 0: "
                         f"{arrivals}")
    rng = np.random.default_rng([seed, 0xA221])
    shape = 1.0 / (cv * cv)
    n = int(rate * seconds * 1.2) + 64
    due = np.cumsum(rng.gamma(shape, 1.0 / (rate * shape), n))
    while due[-1] < seconds:            # a long run of short gaps
        more = rng.gamma(shape, 1.0 / (rate * shape), n)
        due = np.concatenate([due, due[-1] + np.cumsum(more)])
    return due[due < seconds]


def image_ring(n, batch, shape, seed):
    """`n` host float32 batches of (batch,) + shape: the windows
    [i, i + batch) over ONE draw of batch + n - 1 images of 8-bit pixels,
    centred and scaled to about unit variance as a decoder would. Every
    batch is its own contiguous stretch of host memory, so each step pays
    a whole host-to-device copy; drawing n separate batches would cost
    seconds of set-up in page faults and buy nothing a step can see."""
    rng = np.random.default_rng([seed, 0x1AA6])
    pixels = rng.integers(0, 256, (batch + n - 1,) + tuple(shape), np.uint8)
    base = pixels.astype(np.float32)
    base -= 127.5               # in place: the ring is hundreds of MB
    base /= 73.9
    return [base[i:i + batch] for i in range(n)]


def label_ring(n, batch, classes, seed):
    """Class indices as float32, the dtype train_imagenet.py feeds."""
    rng = np.random.default_rng([seed, 0x1ABE])
    return rng.integers(0, classes, (n, batch)).astype(np.float32)
