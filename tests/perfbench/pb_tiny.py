"""Test-only sizes for the benchmark's drivers: the same code paths as the
cells of BENCHMARK.json at sizes a CPU finishes in seconds. Never measured,
never run through the measuring command."""
import copy
import json
import math
import os

from perfbench import cells, run as runmod

RESNET = dict(
    name="tiny-resnet", family="resnet", item="image", network="resnet18_v1",
    unit="basic", expansion=1, stage_units=[2, 2, 2, 2],
    stage_widths=[64, 128, 256, 512],
    stem=dict(kernel=7, stride=2, channels=64, maxpool=[3, 2]), classes=10,
    image_shape=[3, 32, 32], bn_eps=1e-5)
LM = dict(name="tiny-lm", family="transformer_lm", item="token", n_layers=2,
          d_model=64, n_heads=4, d_ff=128, vocab_size=128, max_len=64)
TRAIN = dict(
    driver="train", batch_per_chip=4, dtype="bfloat16", lr=0.02,
    momentum=0.9, wd=1e-4, ring=3, prefetch=2, loss_every=3, warmup_steps=3,
    warmup_loss_band=dict(step=1, min=0.0, max=100.0), check_items=4,
    trace=dict(steps=4, synced_steps=3))
TRAIN_LM = dict(
    driver="train", batch_per_chip=2, seq_len=64, dtype="bfloat16",
    remat=True, lr=1e-3, ring=2, loss_every=2, warmup_steps=3,
    warmup_loss_band=dict(step=1, min=0.0, max=100.0), check_items=2,
    trace=dict(steps=3, synced_steps=2))
SERVE = dict(driver="open_loop", arrivals=dict(rate_per_s=40.0, cv=1.0),
             ladder=[1, 4], ring=8, drain_s=10.0, check_items=4,
             trace=dict(slice_s=0.5))

DEVICE = {"platform": "cpu", "kind": "test-only", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12}


# the serving cell's metrics: files are kept, BENCHMARK.json lists no such
# cell yet (PERF.md section 7)
SERVE_E2E = [dict(name=n, unit="ms", better="lower", source="host_clock")
             for n in ("req_p50_ms", "req_p95_ms")] + \
    [dict(name="setup_s", unit="s", better="lower", source="host_clock")]
SERVE_LAYERS = ("gen_late_p95_ms", "batch_occupancy", "rows_per_dispatch",
                "serve_device_idle_share", "setup_compile_s")


def cell(name, chips, config, traffic, like=None):
    """A Cell with test-only sizes and the metric lists of the real cell
    `like` (or, with none, of the serving cell that is kept for later)."""
    if like is None:
        e2e = SERVE_E2E
        layers = [dict(cells.load_json(os.path.join(
            cells.HERE, "layer_metrics", n + ".json")), name=n)
            for n in SERVE_LAYERS]
    else:
        real = cells.resolve(like)
        e2e, layers = real.end_to_end, real.per_layer
    return cells.Cell(name=name, chips=chips, config=copy.deepcopy(config),
                      traffic=copy.deepcopy(traffic), end_to_end=e2e,
                      per_layer=layers, root=cells.ROOT)


def measure(cell, tmp_path, trace=0, seconds=1.5):
    return runmod.measure(cell, seed=3, seconds=seconds, trace=trace,
                          out_dir=str(tmp_path),
                          devices=dict(DEVICE, count=cell.chips),
                          peaks=PEAKS)


def check_line(line, cell, seconds):
    """The contract's last line, as far as a CPU run can show it."""
    json.loads(json.dumps(line))                        # plain JSON
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
        assert isinstance(m["value"], float) and math.isfinite(m["value"]) \
            and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == cell.chips
