"""Kernels: share of device busy time in the attention kernels alone, by
their names inside the program: `flash_fwd`, `flash_bwd_dq`, `flash_bwd_dkv`
(a full layer's three) and `flash_win_fwd`, `flash_win_bwd_dq`,
`flash_win_bwd_dkv` (a windowed layer's). `flash_time_share` counts every
custom call, which in a step with an expert layer takes in the grouped
products' kernels too; this one does not."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_win_fwd",
           "flash_win_bwd_dq", "flash_win_bwd_dkv")


def read(run):
    named = op_scopes.has_word(*KERNELS)
    return op_scopes.share(run, lambda r: r["category"] == "custom-call"
                           and named(r)) or None
