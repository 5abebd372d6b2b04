"""Executable cache: the self time of `mx:exec_lookup` (`_CachedJit`
flattening the step's arguments into a signature, the memo, the memory tier,
two locks, the compile table) a step of the traced slice. None where the
trace holds no `mx:` span (perfbench/host_spans.py)."""
from perfbench import host_spans

META = {"layer": "executable_cache", "moves": "train_items_per_s",
        "unit": "ms", "better": "lower", "source": "device_trace"}


def read(run):
    return host_spans.per_step_ms(run, lambda reduced: host_spans.span_seconds(
        reduced, host_spans.PREFIX + "exec_lookup", "self_s"))
