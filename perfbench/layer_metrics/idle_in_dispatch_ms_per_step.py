"""Device: the worst chip's idle time under `mx:train_step` and its children,
a step of the traced slice: what a faster dispatch could give back. None
where the trace holds no `mx:` span (perfbench/host_spans.py)."""
from perfbench import host_spans

META = {"layer": "device", "moves": "train_items_per_s", "unit": "ms",
        "better": "lower", "source": "device_trace"}


def read(run):
    return host_spans.per_step_ms(run,
                                  lambda reduced: reduced["idle_in_step_s"])
