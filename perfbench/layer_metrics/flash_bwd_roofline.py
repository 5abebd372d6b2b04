"""Kernels: the least time one layer's attention backward could take
(4 BH T^2 D FLOPs over `flash_bwd_dq` and `flash_bwd_dkv` TOGETHER: the
scores both recompute are not required work; every array once) over the mean
time of a dq call plus a dk/dv call (perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "higher", "source": "device_trace"}


def read(run):
    return op_scopes.flash_roofline(run, backward=True)
