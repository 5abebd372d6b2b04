"""Kernels: share of device busy time in operations under `short_conv`
(a "conv" layer's mixer: the input projection to B, C and x~, the gated
taps, the output projection), forward, recomputed and backward. A fusion
counts by the scope of its root (perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}


def read(run):
    return op_scopes.share(run, op_scopes.has_word("short_conv")) or None
