"""Kernels: share of device busy time in operations under `lightning`
(the chunked decayed linear-attention scan of parallel/linear_attention.py:
the chunk products, the states between chunks), forward, recomputed and
backward. QK-norm, rotary, the output norm and the gate round it are
`gate_norm_time_share`'s. A fusion counts by the scope of its root
(perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}


def read(run):
    return op_scopes.share(run, op_scopes.has_word("lightning")) or None
