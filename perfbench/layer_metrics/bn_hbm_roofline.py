"""Kernels: the bytes XLA says the BatchNorm operations move to and from
HBM (the HBM part of `memory_access_breakdown`, times calls; collectives
left out) over the seconds they took, as a share of the chip's HBM peak.
`bytes_accessed` whole would count operands XLA keeps in on-chip memory
as well (perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "higher", "source": "device_trace"}


def read(run):
    return op_scopes.bn_hbm_roofline(run)
