"""Records the fixture `tests/perfbench/data/hostspans1.xplane.pb.gz` and its
`hostspans1.json`, on the chip:

    chiprun -- python3 tests/perfbench/make_hostspans1.py

then copy `chiprun_out/hostspans1/hostspans1.*` into `tests/perfbench/data/`.

A small Gluon net (two Dense layers) through `parallel.TrainStep`, fed by
`io.prefetch_to_device` from a ring of host batches, as the ResNet cells are:
six steps in one traced slice under the benchmark's own spans (`wait_input`,
`dispatch`, `read_loss` every third step), so the trace holds the program's
`mx:` spans on the dispatching thread and `mx:prefetch_place` on the
worker's, beside a device that is idle most of the time (the steps are tiny:
this is a fixture for the arithmetic, not a measurement). `hostspans1.json`
keeps what this run itself read (`perfbench.host_spans`, `trace_reduce`, the
six metric files) and the program's set-up rows with the instant they were
cut at, which `tests/perfbench/test_pb_host_spans.py` works out again.
"""
from __future__ import annotations

import gzip
import itertools
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
STEPS, READ_EVERY, BATCH, WIDTH = 6, 3, 64, 256
METRICS = ("setup_trace_s", "setup_lower_s", "setup_import_s",
           "dispatch_host_ms_per_step", "exec_lookup_ms_per_step",
           "idle_in_dispatch_ms_per_step")


def build():
    import jax.numpy as jnp
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.io.prefetch import prefetch_to_device
    from incubator_mxnet_tpu.parallel import TrainStep
    mx.random.seed(38)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(WIDTH, activation="relu", in_units=WIDTH),
            gluon.nn.Dense(16, in_units=WIDTH))
    net.initialize()
    step = TrainStep(net, lambda out, label: jnp.mean((out - label) ** 2),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.01,
                                       "momentum": 0.9},
                     example_inputs=[mx.nd.ones((BATCH, WIDTH))],
                     dtype="bfloat16")
    rs = np.random.RandomState(38)
    ring = [(rs.randn(BATCH, WIDTH).astype(np.float32),
             rs.randn(BATCH, 16).astype(np.float32)) for _ in range(4)]
    return step, prefetch_to_device(itertools.cycle(ring), size=2)


def main(out_dir):
    import jax
    from perfbench import cells, host_spans, trace_reduce
    from perfbench.spans import Spans, traced_slice
    born = host_spans.process_born()
    step, feed = build()
    for _ in range(3):
        loss = step(*next(feed))
    loss.block_until_ready()
    setup_s = time.time() - born
    # the readers look under <root>/perfbench/out/<cell>/trace
    cell = cells.Cell(name="hostspans1", chips=1, config={}, traffic={},
                      end_to_end=[], per_layer=[], root=out_dir)
    cell_dir = os.path.join(out_dir, "perfbench", "out", cell.name)
    shutil.rmtree(cell_dir, ignore_errors=True)
    os.makedirs(cell_dir)
    spans = Spans(on=True)

    def body():
        for i in range(STEPS):
            with spans("wait_input"):
                x, y = next(feed)
            with spans("dispatch"):
                loss = step(x, y)
            if (i + 1) % READ_EVERY == 0:
                with spans("read_loss"):
                    float(loss)
    try:
        _, anchor = traced_slice(os.path.join(cell_dir, "trace"), body)
    finally:
        feed.close()
    path = trace_reduce.find_xplane(os.path.join(cell_dir, "trace"))
    with open(path, "rb") as f, gzip.open(
            os.path.join(out_dir, "hostspans1.xplane.pb.gz"), "wb", 9) as g:
        g.write(f.read())
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"     # a rehearsal has no device plane
    summary = trace_reduce.reduce_trace(
        trace_reduce.load(path), spans=spans.rows,
        anchor_bench=anchor) if on_chip else None
    rows = host_spans.program_table()["rows"]
    run = {"cell": cell, "peaks": {}, "trace": summary, "driver": {},
           "e2e": {"setup_s": setup_s}, "counters": None}
    read = {n: cells.layer_metric_reader(n)(run) for n in METRICS}
    record = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "steps": STEPS, "read_every": READ_EVERY,
        "anchor": anchor, "bench_spans": spans.rows,
        "bench_idle_gaps": summary and summary["idle_gaps"],
        "idle_share_worst": summary and summary["idle_share_worst"],
        "read": read, "spans": host_spans.of(run),
        "setup": host_spans.setup_of(run), "setup_s": setup_s,
        # seconds after the process's birth, to the microsecond
        "rows": [[ph, name, round(t0 - born, 6), round(t1 - born, 6)]
                 for ph, name, t0, t1 in rows]}
    with open(os.path.join(out_dir, "hostspans1.json"), "w") as f:
        json.dump(record, f, indent=0)
    sizes = [os.path.getsize(os.path.join(out_dir, "hostspans1" + ext))
             for ext in (".xplane.pb.gz", ".json")]
    print(json.dumps({k: v for k, v in record.items() if k != "rows"},
                     indent=1))
    print(f"[hostspans1] {sizes} bytes (trace gzipped, record); together "
          "the fixture stays under 200 KB")
    return 0 if on_chip and sum(sizes) < 200_000 else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "hostspans1")))
