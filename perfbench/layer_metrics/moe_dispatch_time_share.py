"""Kernels: share of device busy time in operations under `moe_route`
(scores, top-k, weights), `moe_dispatch` (the sort of the token slots by
local expert, the gather of the held rows) or `moe_combine` (rows weighted
and summed back into their tokens): what routing costs beside the experts'
products, forward, recomputed and backward. A fusion counts by the scope of
its root (perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}


def read(run):
    return op_scopes.share(run, op_scopes.has_word(
        "moe_route", "moe_dispatch", "moe_combine")) or None
