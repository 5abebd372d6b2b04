"""Find an open-loop cell's knee, once, on the chip: one process builds
the cell's server and offers Poisson (or the mix's own) arrivals at each
rate in turn. The knee is the highest rate whose p95 meets the limit with
nothing shed and no growing backlog (the second half of the run no slower
than the first); the cell's traffic file then fixes 0.8 of it and records
this sweep.

    python3 -m perfbench.tools.sweep --workload resnet50.predict-poisson \\
        --rates 200 400 800 1200 1600 --seconds 8 [--limit-ms 100]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from .. import cells, run as runmod, traffic
from ..drivers import open_loop
from ..spans import Spans


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--limit-ms", type=float, default=100.0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    runmod.point_caches_into_checkout(cell.name)
    sys.path.insert(0, cells.ROOT)
    import jax
    runmod.check_devices(jax.devices(), cell.chips)
    job = cell.family.ServeJob(cell, args.seed, Spans(on=False))
    rows = []
    try:
        open_loop.warm_up(job)
        for rate in args.rates:
            arrivals = dict(cell.traffic["arrivals"], rate_per_s=rate)
            due = traffic.arrival_offsets(arrivals, args.seconds, args.seed)
            before = job.counters()
            s = open_loop.summarize(open_loop.play(
                job, list(due), cell.traffic["drain_s"]))
            after = job.counters()
            s["rate_per_s"] = rate
            s["rows_per_dispatch"] = (
                after["serve.responses_ok"] - before["serve.responses_ok"]) \
                / max(after["serve.batches_total"]
                      - before["serve.batches_total"], 1)
            s["sustained"] = bool(
                s["failed"] == 0 and s["req_p95_ms"] <= args.limit_ms
                and s["p50_second_half_ms"] <= 1.5 * s["p50_first_half_ms"]
                + 1.0)
            rows.append(s)
            print(json.dumps(s), flush=True)
    finally:
        job.close()
    good = [r["rate_per_s"] for r in rows if r["sustained"]]
    knee = max(good) if good else None
    print(json.dumps({"knee_per_s": knee, "limit_ms": args.limit_ms,
                      "seconds_per_rate": args.seconds,
                      "rate_at_four_fifths": knee and 0.8 * knee}))
    out = os.path.join(cells.HERE, "out", cell.name)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "sweep.json"), "w") as f:
        json.dump({"rows": rows, "knee_per_s": knee}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
