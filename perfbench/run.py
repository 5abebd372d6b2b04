"""One cell, one process, one JSON line.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Builds the cell from BENCHMARK.json and the files it names, warms up the
cell's own shapes, measures for `--seconds`, checks the outputs against
the plain reference, and prints the result as the last line of standard
output. Everything else a run has to say goes on earlier lines and into
perfbench/out/<cell>/. No TPU, a device that peaks.json does not list, or
another number of chips than the cell asks for: a non-zero exit and no
result line. This process holds the chip; it starts no other.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

from . import cells, counters as counters_mod, flops
from .cells import BenchError
from .spans import Spans


def process_age_s():
    """Seconds since this process was created (imports of the interpreter
    and of this package included); 0 where /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


def point_caches_into_checkout(cell_name):
    """jax's persistent cache and the program's executable cache at fixed
    paths of this checkout, one pair per cell (the path is part of the
    cache's key; a cell's first run here compiles, the rest load). Set
    before the program is imported: it reads both at import."""
    base = os.path.join(cells.HERE, ".cache", cell_name)
    for var, sub in (("JAX_COMPILATION_CACHE_DIR", "jax"),
                     ("MXNET_EXEC_CACHE_DIR", "exec")):
        os.environ[var] = os.path.join(base, sub)
        os.makedirs(os.environ[var], exist_ok=True)


def check_devices(devices, chips):
    """The device record of the result line; BenchError unless these are
    exactly `chips` TPU chips of a kind peaks.json lists."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"no TPU: jax came up on platform "
                         f"{dev.platform!r} ({dev.device_kind}); the "
                         f"benchmark measures the chip and nothing else")
    peaks = flops.device_peaks(dev.device_kind)
    if len(devices) != chips:
        raise BenchError(f"the cell asks for {chips} chip(s); jax sees "
                         f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}, peaks


def memory_held_bytes(devices):
    """Bytes the runtime holds right now on the fullest chip: live buffers
    plus what it has reserved for the temporaries of loaded programs. On
    this runtime (jax 0.9.0, libtpu 0.0.34) a program's temporaries are
    `bytes_reserved` and never show in `bytes_in_use` or its peak: a
    GPT-2 medium step whose compiler reserves 10.0 GB read 5.6 GB there."""
    held = [0]
    for d in devices:
        s = d.memory_stats() or {}
        held.append(s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0))
    return max(held)


def memory_peak_bytes(devices, sampled):
    """The peak on the fullest chip: the runtime's own peak of live bytes,
    or the most it held (live + reserved) at the window's two ends, where
    the job is alive and its step loaded, whichever is larger."""
    stats = [d.memory_stats() or {} for d in devices]
    print(f"[perfbench] memory_stats per device: {stats}; most held at the "
          f"window's ends: {sampled}", flush=True)
    return max([sampled] + [s.get("peak_bytes_in_use", 0) for s in stats])


class Run:
    """What a driver is handed: the cell, the arguments, the benchmark's
    spans and counters, and the two marks that bound the window."""

    def __init__(self, cell, seed, seconds, trace, out_dir, jax_devices=()):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.jax_devices, self.memory_held = jax_devices, 0
        self.trace = bool(trace)
        self.out_dir = out_dir
        self.trace_dir = os.path.join(out_dir, "trace")
        self.spans = Spans(on=self.trace)
        self.counters = counters_mod.Counters()
        self.setup_s = None
        self._born = time.perf_counter() - process_age_s()

    def log(self, msg):
        """A line for the reader of the log, stamped with the seconds since
        the process began: together they are the set-up's split."""
        age = time.perf_counter() - self._born
        print(f"[perfbench:{self.cell.name} +{age:.1f}s] {msg}", flush=True)

    def start_window(self):
        self.memory_held = memory_held_bytes(self.jax_devices)
        self.counters.mark("window_start")
        self.setup_s = time.perf_counter() - self._born
        self.log(f"set-up {self.setup_s:.1f} s; window of "
                 f"{self.seconds} s begins")

    def end_window(self):
        self.counters.mark("window_end")
        self.memory_held = max(self.memory_held,
                               memory_held_bytes(self.jax_devices))


def measure(cell, seed, seconds, trace, out_dir, devices, peaks,
            jax_devices=()):
    """Run the cell's driver and reduce what it returns to the result
    line's `metrics` (and, traced, `device` additions and `breakdown`)."""
    r = Run(cell, seed, seconds, trace, out_dir, jax_devices)
    shutil.rmtree(r.trace_dir, ignore_errors=True)      # one trace at a time
    r.log(f"devices up: {devices}; seed {seed}, {seconds} s, trace "
          f"{int(trace)}")
    builds = counters_mod.JaxBuilds().install()
    r.counters.add_source("jax", builds.read)
    r.counters.add_source("", counters_mod.program_counters)
    try:
        got = cell.driver.run(r)
    finally:
        builds.uninstall()
    got["e2e"]["setup_s"] = r.setup_s
    extra, breakdown, summary = {}, None, None
    wanted = cell.per_layer if r.trace else cell.end_to_end
    if r.trace:
        from . import trace_reduce
        summary = trace_reduce.reduce_trace(
            trace_reduce.load(trace_reduce.find_xplane(r.trace_dir)),
            spans=r.spans.rows, anchor_bench=got["anchor"])
        extra = {"busy_s": summary["busy_s"],
                 "window_s": summary["window_s"]}
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary.get("idle_gaps", [])}
        record = {"counters": r.counters, "trace": summary,
                  "driver": got["driver"], "e2e": got["e2e"], "cell": cell,
                  "peaks": peaks}
        values = {m["name"]: cells.layer_metric_reader(
            m["name"], cell.root)(record) for m in wanted}
    else:
        values = {m["name"]: got["e2e"].get(m["name"]) for m in wanted}
        missing = [k for k, v in values.items() if v is None]
        if missing:
            raise BenchError(f"driver {cell.traffic['driver']!r} reported "
                             f"no {missing} for cell {cell.name!r}")
    units = {m["name"]: m["unit"] for m in wanted}
    metrics = {k: {"value": v, "unit": units[k]}
               for k, v in values.items() if v is not None}
    for p in got["problems"]:
        r.log(f"NOT CORRECT: {p}")
    with open(os.path.join(out_dir, f"run_seed{seed}_trace{int(trace)}.json"),
              "w") as f:
        json.dump({"driver": got["driver"], "e2e": got["e2e"],
                   "marks": r.counters.marks, "problems": got["problems"],
                   "trace": summary}, f, indent=1, default=str)
    line = {"correct": not got["problems"], "attempted": got["attempted"],
            "failed": got["failed"], "metrics": metrics,
            "device": dict(devices, memory_peak_bytes=memory_peak_bytes(
                jax_devices, r.memory_held), **extra)}
    if breakdown:
        line["breakdown"] = breakdown
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.resolve(args.workload)
        point_caches_into_checkout(cell.name)
        out_dir = os.path.join(cells.HERE, "out", cell.name)
        os.makedirs(out_dir, exist_ok=True)
        sys.path.insert(0, cells.ROOT)      # the program of THIS checkout
        import jax
        import incubator_mxnet_tpu
        if not os.path.abspath(incubator_mxnet_tpu.__file__).startswith(
                cells.ROOT + os.sep):
            raise BenchError("incubator_mxnet_tpu was imported from "
                             f"{incubator_mxnet_tpu.__file__}, not from "
                             f"this checkout ({cells.ROOT})")
        devices = jax.devices()
        device, peaks = check_devices(devices, cell.chips)
        line = measure(cell, args.seed, args.seconds, args.trace, out_dir,
                       device, peaks, devices)
    except BenchError as e:
        print(f"[perfbench] {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
