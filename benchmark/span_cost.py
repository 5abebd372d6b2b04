"""What a `profiler.span` costs the thread that enters it, in nanoseconds a
span, on whatever host this runs on (PERF.md section 6, PR 38):

    chiprun -- python3 benchmark/span_cost.py

Three states: no profiler session and the gate off (the hot path of every
step: a shared no-op after one read of TraceMe's flag), a session recording
(a `jax.profiler.TraceAnnotation`), and the gate on with no session (the
booked span). Host clock, best of five loops of 200,000 (20,000 while a
session keeps every span): a floor, which is what an overhead per span is. Prints one JSON line.
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
N, ROUNDS = 200_000, 5


def per_span_ns(enter, n=N):
    best = float("inf")
    for _ in range(ROUNDS):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with enter("compute"):
                pass
        best = min(best, (time.perf_counter_ns() - t0) / n)
    t0 = time.perf_counter_ns()
    for _ in range(n):
        pass
    return best - (time.perf_counter_ns() - t0) / n


def main():
    import jax
    from incubator_mxnet_tpu import profiler
    out = {"host": os.uname().nodename, "platform": jax.devices()[0].platform}
    profiler.attribution_enable(False)
    out["off_no_session_ns"] = per_span_ns(profiler.span)
    out["bare_annotation_no_session_ns"] = per_span_ns(
        jax.profiler.TraceAnnotation)
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            # a tenth of the loop: every one of these is kept by the session
            out["off_recording_ns"] = per_span_ns(profiler.span, N // 10)
        finally:
            jax.profiler.stop_trace()
    profiler.attribution_enable(True)
    out["gate_on_no_session_ns"] = per_span_ns(profiler.span)
    profiler.attribution_enable(False)
    profiler.dumps(reset=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
