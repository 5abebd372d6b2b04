"""Kernels: share of device busy time in operations under `norm` (RMSNorm
before each half of a block and before the head, QK-norm, the output norm
over all heads), `rope` (rotary on q and k) or `gate` (the sigmoid output
gates): the bandwidth work round the mixers, forward, recomputed and
backward. A fusion counts by the scope of its root: what XLA fuses into a
neighbouring matrix product is that product's (perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}


def read(run):
    return op_scopes.share(run, op_scopes.has_word("norm", "rope",
                                                   "gate")) or None
