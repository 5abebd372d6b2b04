"""Kernels: share of device busy time in operations under `moe` (an expert
layer's MLP: the router, the sort and gather of the held token slots, the
grouped products of the held experts, the shared expert, the weighted sum
back into the tokens), forward, recomputed and backward. A fusion counts by
the scope of its root (perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}


def read(run):
    return op_scopes.share(run, op_scopes.has_word("moe")) or None
