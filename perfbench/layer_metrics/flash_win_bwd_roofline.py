"""Kernels: the least time one layer's windowed attention backward could
take (`flash_win_bwd_dq` and `flash_win_bwd_dkv` TOGETHER: 4 (Cqk + Cv) a
kept (i, j) pair; the scores both recompute are not required work; every
array once) over the mean time of a dq call plus a dk/dv call. The counting
functions are `flash_win_fwd_roofline.py`'s."""
import importlib.util
import os

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "higher", "source": "device_trace"}

_spec = importlib.util.spec_from_file_location(
    "perfbench.layer_metrics.flash_win_fwd_roofline", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "flash_win_fwd_roofline.py"))
forward = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(forward)


def read(run):
    return forward.roofline(run, backward=True)
