"""Train step: share of device busy time in operations with none of
`forward`, `loss`, `optimizer` in their path: what the program's names
do not reach (copies XLA adds, a code path nobody named). It guards the six
metrics that read the names against decay; None where NO operation has a
name (perfbench/op_scopes.py)."""
from perfbench import op_scopes

META = {"layer": "train_step", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}


def read(run):
    return op_scopes.share(run, lambda row: not op_scopes.top_word(row))
