"""Kernels: share of device busy time in operations under `BatchNorm` or
`FusedBNAddReLU` (the registry's names; the zoo's residual blocks call the
second), forward and backward, collectives among them. A convolution fused
with a BatchNorm epilogue is ONE operation and counts where its root is
(perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}


def read(run):
    return op_scopes.share(run, op_scopes.has_word(*op_scopes.BN_WORDS))
