"""Train step: share of device busy time in RECOMPUTED operations, those
jax wrote under `rematted_computation` (the forward pass that
`jax.checkpoint` repeats inside the backward pass). A fusion counts by the
scope of its root (perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "train_step", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}


def read(run):
    return op_scopes.share(run, lambda row: row["recomputed"])
