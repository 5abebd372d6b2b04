"""Run one cell several times, a new process and a new seed each, and
print each metric's median and spread (distance between the quartiles
over the median) as the driver reads them.

    python3 -m perfbench.tools.repeat --workload resnet50.train \\
        --seeds 1 2 3 4 5 6 [--trace 0] [--out DIR]

The first run of a cell in a checkout compiles: its set-up is printed
apart and left out of setup_s's median. Exit code 1 if any run failed or
was not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from .. import cells, stats


def run_once(workload, seed, seconds, trace, log):
    cmd = [sys.executable, "-m", "perfbench.run", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=cells.ROOT, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True, timeout=1500)
    log.write(proc.stdout)
    log.flush()
    last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
    line = None
    if proc.returncode == 0:
        try:
            line = json.loads(last)
        except ValueError:
            pass
    return {"seed": seed, "rc": proc.returncode,
            "wall_s": time.time() - t0, "line": line}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float,
                    default=cells.load_benchmark()["run_seconds"])
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out = args.out or os.path.join(cells.HERE, "out", args.workload)
    os.makedirs(out, exist_ok=True)
    tag = f"{args.workload}.trace{args.trace}"
    runs = []
    with open(os.path.join(out, tag + ".log"), "a") as log, \
            open(os.path.join(out, tag + ".jsonl"), "a") as rows:
        for seed in args.seeds:
            r = run_once(args.workload, seed, args.seconds, args.trace, log)
            rows.write(json.dumps(r) + "\n")
            rows.flush()
            ok = r["line"] is not None and r["line"]["correct"]
            print(f"seed {seed}: rc {r['rc']} wall {r['wall_s']:.0f} s "
                  + (json.dumps(r["line"]) if r["line"] else "NO RESULT"),
                  flush=True)
            runs.append((r, ok))
    good = [r["line"] for r, ok in runs if ok]
    names = sorted({k for ln in good for k in ln["metrics"]})
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in good
                if name in ln["metrics"]]
        if name == "setup_s" and len(vals) > 1:
            print(f"setup_s of the first run: {vals[0]:.1f} s")
            vals = vals[1:]
        sp = stats.spread(vals)
        print(f"{name}: n={len(vals)} median={stats.median(vals):.6g} "
              f"spread={'n/a' if sp is None else f'{sp:.4%}'} "
              f"values={[round(v, 4) for v in vals]}")
    return 0 if len(good) == len(runs) else 1


if __name__ == "__main__":
    sys.exit(main())
