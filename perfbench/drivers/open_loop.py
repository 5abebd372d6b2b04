"""Requests on a schedule, whether or not earlier ones have finished:
independent users make an open loop.

Traffic keys: `arrivals` {rate_per_s, cv} (traffic.arrival_offsets),
`ring` (seeded inputs cycled through), `drain_s` (how long after the last
arrival an answer may still come), `check_items`, `trace` {slice_s}, and
the family's own (`ladder`, ...). The rate is fixed in the file: the knee
was found once by tools/sweep.py and is recorded beside it.

Every request is timed from the instant it was DUE, on this module's
clock, so a stall's cost to the requests behind it is counted; how late
the generator itself ran is reported beside it.
"""
from __future__ import annotations

import functools
import threading
import time

from .. import stats, traffic
from ..spans import traced_slice


def play(job, due, drain_s):
    """Send request i at t0 + due[i]; returns {"t0", "due", "sent", "done",
    "end"} on the perf_counter clock, done[i] None where request i was
    shed, failed, or not answered `drain_s` after the last arrival."""
    n = len(due)
    sent, done = [None] * n, [None] * n
    pending = threading.Semaphore(0)

    def on_done(i, fut):                # runs on the batcher's thread
        if fut.exception() is None:
            done[i] = time.perf_counter()
        pending.release()

    t0 = time.perf_counter()
    issued = 0
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.perf_counter()
        fut = job.submit(i)
        if fut is not None:
            issued += 1
            fut.add_done_callback(functools.partial(on_done, i))
    deadline = t0 + (due[-1] if n else 0) + drain_s
    for _ in range(issued):
        if not pending.acquire(timeout=max(deadline - time.perf_counter(),
                                           0)):
            break
    return {"t0": t0, "due": [t0 + d for d in due], "sent": sent,
            "done": list(done), "end": time.perf_counter()}


def summarize(rec):
    lat = stats.due_latencies_ms(rec["due"], rec["done"], rec["end"])
    late = [(s - d) * 1e3 for s, d in zip(rec["sent"], rec["due"])]
    half = len(lat) // 2
    return {"attempted": len(lat),
            "failed": sum(d is None for d in rec["done"]),
            "req_p50_ms": stats.percentile(lat, 50),
            "req_p95_ms": stats.percentile(lat, 95),
            "gen_late_p95_ms": stats.percentile(late, 95),
            # a backlog that grows shows as a second half slower than the
            # first
            "p50_first_half_ms": stats.median(lat[:half]),
            "p50_second_half_ms": stats.median(lat[half:])}


def in_flight_spans(rec):
    """("requests_in_flight" | "no_request", t0, t1) rows covering the
    run: whether the program had anything to do when the device idled."""
    edges = []
    for s, d in zip(rec["sent"], rec["done"]):
        edges += [(s, 1), (d if d is not None else rec["end"], -1)]
    rows, depth, last = [], 0, rec["t0"]
    for t, step in sorted(edges):
        if t > last:
            rows.append(("requests_in_flight" if depth else "no_request",
                         last, t))
            last = t
        depth += step
    rows.append(("no_request", last, rec["end"]))
    return rows


def warm_up(job):
    """One burst per bucket of the ladder, each awaited: every executable
    and every upload shape has run before the window."""
    for n in job.warmup_bursts():
        for fut in [job.submit(i) for i in range(n)]:
            fut.result(timeout=120)


def run(r):
    mix = r.cell.traffic
    job = r.cell.family.ServeJob(r.cell, r.seed, r.spans)
    r.counters.add_source("", job.counters)
    r.log("job built")
    try:
        warm_up(job)
        check = job.check(mix["check_items"])
        r.log(f"against the reference: {check}")
        due = traffic.arrival_offsets(mix["arrivals"], r.seconds, r.seed)

        r.start_window()
        anchor, tracer = None, None
        if r.trace:
            box = {}

            def trace_mid_window():
                time.sleep(r.seconds / 2)
                _, box["anchor"] = traced_slice(
                    r.trace_dir, lambda: time.sleep(mix["trace"]["slice_s"]))
            tracer = threading.Thread(target=trace_mid_window,
                                      name="perfbench-tracer")
            tracer.start()
        rec = play(job, list(due), mix["drain_s"])
        if tracer is not None:
            tracer.join()
            anchor = box.get("anchor")
            for row in in_flight_spans(rec):
                r.spans.add(*row)
        r.end_window()
    finally:
        job.close()

    summary = summarize(rec)
    built = r.counters.over("jax.programs_built", "window")
    problems = []
    if not check["ok"]:
        problems.append(f"outputs differ from the reference: {check}")
    if built:
        problems.append(f"{built} programs were built inside the window")
    summary["check"] = check
    return {"attempted": summary["attempted"], "failed": summary["failed"],
            "problems": problems,
            "e2e": {"req_p50_ms": summary["req_p50_ms"],
                    "req_p95_ms": summary["req_p95_ms"]},
            "driver": summary, "anchor": anchor}
