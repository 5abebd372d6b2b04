"""Counters: what jax and the program count, snapshotted at marks.

One flat dict of numbers per mark ("window_start", "window_end"; the
process starts at zero). A per-layer metric that is a counter, or a ratio
of counters, is a JSON file read by `read_data_metric`: adding one is
adding data. README.md lists the counter names.
"""
from __future__ import annotations

BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
HIT_EVENT = "/jax/compilation_cache/cache_hits"


class JaxBuilds:
    """Every executable jax builds in this process, through jax's own
    monitoring hooks: a build is an XLA compile or a load from jax's
    persistent cache (`persistent_hits` of them), and either one inside
    the measured window is a shape the warm-up missed."""

    def __init__(self):
        self.n = {"programs_built": 0, "build_s": 0.0, "traces": 0,
                  "persistent_hits": 0}

    def _duration(self, event, duration_secs, **_):
        if event == BUILD_EVENT:
            self.n["programs_built"] += 1
            self.n["build_s"] += duration_secs
        elif event == TRACE_EVENT:
            self.n["traces"] += 1

    def _event(self, event, **_):
        if event == HIT_EVENT:
            self.n["persistent_hits"] += 1

    def install(self):
        import jax.monitoring as m
        m.register_event_duration_secs_listener(self._duration)
        m.register_event_listener(self._event)
        return self

    def uninstall(self):
        import jax.monitoring as m
        m.unregister_event_duration_listener(self._duration)
        m.unregister_event_listener(self._event)

    def read(self):
        return dict(self.n)


def program_counters():
    """The program's own process-wide counters: compile telemetry, the
    executable cache's tiers, the kernel tuner. Counts only; the tuner's
    `timings_us` are host wall clock around eager dispatches and are not
    read."""
    from incubator_mxnet_tpu import compile_cache, profiler, tune
    out = {}
    rows = profiler.compile_stats().values()
    for k in ("hits", "misses", "disk_hits", "compile_ms"):
        out[f"compile.{k}"] = sum(r[k] for r in rows)
    for k, v in compile_cache.stats().items():
        out[f"exec_cache.{k}"] = v
    for k, v in tune.stats().items():
        out[f"tune.{k}"] = v
    out["tune.nonxla_winners"] = sum(
        r["winner"] != "xla" for r in tune.winners().values())
    return out


class Counters:
    def __init__(self):
        self.sources = []       # (prefix, function returning {name: number})
        self.marks = {}

    def add_source(self, prefix, fn):
        self.sources.append((prefix, fn))

    def snapshot(self):
        snap = {}
        for prefix, fn in self.sources:
            for k, v in fn().items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    snap[f"{prefix}.{k}" if prefix else k] = v
        return snap

    def mark(self, name):
        self.marks[name] = self.snapshot()

    def over(self, counter, span):
        """A counter's change over `span`: "setup" (process start to the
        window), "window", "run" (start to the window's end); or its value
        "at_end". None where the counter does not exist in this cell."""
        a, b = self.marks.get("window_start", {}), \
            self.marks.get("window_end", {})
        if counter not in b:
            return None
        if span == "setup":
            return a.get(counter, 0)
        if span == "window":
            return b[counter] - a.get(counter, 0)
        if span in ("run", "at_end"):
            return b[counter]
        raise ValueError(f"unknown span {span!r} for counter {counter!r}")


def _terms(terms, counters):
    vals = [counters.over(t["counter"], t.get("over", "window"))
            for t in terms]
    return None if any(v is None for v in vals) else sum(vals)


def read_data_metric(spec, run):
    """Value of a data-defined per-layer metric, or None when what it
    reads is not there (the harness then leaves the metric out).

    {"field": "trace.idle_share_worst"}      a number the run computed
    {"num": [terms], "den": [terms]}         a sum of counter changes, or a
        ratio of two; a term is {"counter": name, "over": span}
    Either takes "scale" (default 1)."""
    scale = spec.get("scale", 1)
    if "field" in spec:
        v = run
        for part in spec["field"].split("."):
            v = v.get(part) if isinstance(v, dict) else None
        return None if v is None else v * scale
    num = _terms(spec["num"], run["counters"])
    if num is None:
        return None
    if "den" not in spec:
        return num * scale
    den = _terms(spec["den"], run["counters"])
    return None if not den else num / den * scale
