"""The benchmark's own host spans, kept in memory on its own clock.

Spans sit around the benchmark's calls into the program (wait for input,
dispatch, read back). A traced run relates them to the device trace
through one anchor span that is written both here and, as a
`jax.profiler.TraceAnnotation`, into the profiler's trace.
"""
from __future__ import annotations

import contextlib
import time

ANCHOR = "perfbench:slice"


class Spans:
    """`with spans("dispatch"):` appends (name, t0, t1); off, it is a
    shared no-op, so an untraced run carries no bookkeeping."""

    def __init__(self, on):
        self.on = on
        self.rows = []

    def __call__(self, name):
        return self._record(name) if self.on else contextlib.nullcontext()

    @contextlib.contextmanager
    def _record(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))

    def add(self, name, t0, t1):
        if self.on:
            self.rows.append((name, t0, t1))


def traced_slice(trace_dir, body):
    """Run body() under jax's profiler with the anchor span round it.
    Returns (body's result, the anchor on the benchmark's clock)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # device ops and our anchor only
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        a0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANCHOR):
            out = body()
        a1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    return out, (a0, a1)
