"""Train step: share of device busy time in operations under `logits` or
`loss` (the vocabulary-wide projection, the log-softmax and the gather),
forward and backward. A fusion counts by the scope of its root
(perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "train_step", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}


def read(run):
    return op_scopes.share(run, op_scopes.has_word("logits", "loss"))
