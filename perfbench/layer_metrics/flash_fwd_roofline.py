"""Kernels: the least time one `flash_fwd` call could take on this chip
(2 BH T^2 D FLOPs under the causal mask against the bfloat16 peak, or its
operands and results once against the HBM peak, whichever is longer) over
the mean device time of its calls in the slice, first and recomputed runs
alike. The log says which bounds (perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "higher", "source": "device_trace"}


def read(run):
    return op_scopes.flash_roofline(run)
