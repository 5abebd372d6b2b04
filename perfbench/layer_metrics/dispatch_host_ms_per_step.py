"""Train step: the program's host time for one step, `mx:train_step`
(`TrainStep.__call__`, whole) over the traced slice divided by its calls.
None where the trace holds no `mx:` span (perfbench/host_spans.py)."""
from perfbench import host_spans

META = {"layer": "train_step", "moves": "train_items_per_s", "unit": "ms",
        "better": "lower", "source": "device_trace"}


def read(run):
    return host_spans.per_step_ms(run, lambda reduced: host_spans.span_seconds(
        reduced, host_spans.STEP, "total_s"))
