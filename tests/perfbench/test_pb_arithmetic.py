"""The benchmark's arithmetic: percentiles, due-time latency, arrivals,
required FLOPs, the peaks table, counter metrics defined as data."""
import math
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from perfbench import counters, flops, stats, traffic
from perfbench.cells import ROOT, BenchError, load_json
from perfbench.drivers import open_loop


def test_percentile_is_exact_and_interpolates():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 50) is None
    assert stats.percentile([3, 1, 2], 0) == 1 and \
        stats.percentile([3, 1, 2], 100) == 3


def test_spread_is_interquartile_over_median():
    assert stats.spread([100, 101, 102, 103, 104]) == pytest.approx(2 / 102)
    assert stats.spread([]) is None


def test_unanswered_requests_rank_above_every_answer():
    lat = stats.due_latencies_ms([0.0, 1.0, 2.0], [0.5, None, 2.25], end=10.0)
    assert lat == [500.0, 9000.0, 250.0]


@pytest.mark.parametrize("cv", [1.0, 2.5])
def test_arrivals_are_seeded_and_have_the_rate_and_burstiness(cv):
    spec = {"rate_per_s": 500.0, "cv": cv}
    a = traffic.arrival_offsets(spec, 20.0, seed=3)
    assert np.array_equal(a, traffic.arrival_offsets(spec, 20.0, seed=3))
    assert not np.array_equal(a[:50],
                              traffic.arrival_offsets(spec, 20.0, seed=4)[:50])
    assert a[0] > 0 and a[-1] < 20.0 and np.all(np.diff(a) >= 0)
    gaps = np.diff(a)
    assert len(a) / 20.0 == pytest.approx(500.0, rel=0.1)
    assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.15)


def test_image_ring_is_seeded_contiguous_windows():
    ring = traffic.image_ring(3, 4, (3, 8, 8), seed=5)
    again = traffic.image_ring(3, 4, (3, 8, 8), seed=5)
    assert len(ring) == 3 and ring[0].shape == (4, 3, 8, 8)
    assert ring[0].dtype == np.float32 and ring[1].flags["C_CONTIGUOUS"]
    assert all(np.array_equal(a, b) for a, b in zip(ring, again))
    assert np.array_equal(ring[0][1:], ring[1][:-1])
    assert abs(float(ring[0].mean())) < 0.3 and \
        0.7 < float(ring[0].std()) < 1.3


class _StallingJob:
    """Answers each request `service` seconds after it was sent, on one
    worker thread, except that the worker stalls once: what a batcher
    thread blocked on a long dispatch does to the requests behind it."""

    def __init__(self, service, stall_at, stall_s):
        self.service, self.stall_at, self.stall_s = service, stall_at, stall_s
        self.queue, self.cv = [], threading.Condition()
        self.thread = threading.Thread(target=self._work, daemon=True)
        self.thread.start()

    def submit(self, i):
        if i == 3:
            return None                 # shed at admission
        fut = Future()
        with self.cv:
            self.queue.append((i, fut))
            self.cv.notify()
        return fut

    def _work(self):
        while True:
            with self.cv:
                while not self.queue:
                    self.cv.wait()
                i, fut = self.queue.pop(0)
            if i is None:
                return
            time.sleep(self.stall_s if i == self.stall_at else self.service)
            fut.set_result([i])

    def close(self):
        with self.cv:
            self.queue.append((None, None))
            self.cv.notify()
        self.thread.join(timeout=5)
        assert not self.thread.is_alive()


def test_open_loop_times_from_due_and_counts_the_stall_and_the_shed():
    due = [0.02 * i for i in range(40)]             # 50 requests a second
    job = _StallingJob(service=0.002, stall_at=10, stall_s=0.3)
    try:
        rec = open_loop.play(job, due, drain_s=5.0)
    finally:
        job.close()
    s = open_loop.summarize(rec)
    assert s["attempted"] == 40 and s["failed"] == 1
    lat = stats.due_latencies_ms(rec["due"], rec["done"], rec["end"])
    # the stalled request, and those queued behind it, waited from when
    # they were DUE: the one due 20 ms after the stall began still saw
    # about 280 ms of it
    assert lat[10] >= 300 and 250 <= lat[11] <= 400
    assert lat[9] < 100 and lat[39] < 100           # before it, and drained
    assert lat[3] == pytest.approx((rec["end"] - rec["due"][3]) * 1e3)
    assert s["req_p95_ms"] > 200 > s["req_p50_ms"]
    assert 0 <= s["gen_late_p95_ms"] < 20
    rows = open_loop.in_flight_spans(rec)
    assert {r[0] for r in rows} == {"requests_in_flight", "no_request"}
    assert all(b[1] == a[2] for a, b in zip(rows, rows[1:]))  # no holes
    busy = sum(t1 - t0 for n, t0, t1 in rows if n == "requests_in_flight")
    assert busy >= 0.3


def test_flops_tables():
    r50 = load_json(ROOT + "/perfbench/configs/resnet50.json")
    assert flops.resnet_forward_macs(r50) == r50["forward_multiply_adds"] \
        == 3_857_973_248
    assert flops.resnet_train_flops_per_item(r50) == 6 * 3_857_973_248
    gpt = load_json(ROOT + "/perfbench/configs/gpt2-medium.json")
    assert flops.transformer_matmul_params(gpt) == 353_453_056
    per_token = flops.transformer_train_flops_per_item(gpt, 1024)
    assert per_token == 6 * 353_453_056 + 6 * 24 * 1024 * 1024
    assert per_token / 1e9 == pytest.approx(2.27, abs=0.005)
    # causal attention once: the unmasked count would add as much again
    assert flops.transformer_train_flops_per_item(gpt, 8192) - \
        6 * 353_453_056 == 8 * (per_token - 6 * 353_453_056)


def test_unknown_device_kind_has_no_peaks():
    assert flops.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.device_peaks("TPU v5 lite")["ici_bits_per_s"] == 1600e9
    for kind in ("TPU v4", "cpu", "source"):
        with pytest.raises(BenchError, match="no published peaks"):
            flops.device_peaks(kind)


def _counters(start, end):
    c = counters.Counters()
    c.marks = {"window_start": start, "window_end": end}
    return c


def test_data_defined_metrics_read_counters_and_fields():
    c = _counters({"serve.responses_ok": 10, "serve.padded_rows_total": 30,
                   "jax.build_s": 12.5, "tune.searches": 4},
                  {"serve.responses_ok": 110, "serve.padded_rows_total": 55,
                   "jax.build_s": 12.5, "tune.searches": 4, "late.only": 2})
    run = {"counters": c, "trace": {"idle_share_worst": 0.25}, "driver": {}}
    occupancy = load_json(ROOT + "/perfbench/layer_metrics/batch_occupancy.json")
    assert counters.read_data_metric(occupancy, run) == \
        pytest.approx(100 * 100 / 125)
    assert counters.read_data_metric(
        load_json(ROOT + "/perfbench/layer_metrics/setup_compile_s.json"), run) == 12.5
    assert counters.read_data_metric(
        load_json(ROOT + "/perfbench/layer_metrics/tuner_searches.json"), run) == 4
    assert counters.read_data_metric(
        load_json(ROOT + "/perfbench/layer_metrics/device_idle_share.json"), run) == 25
    # what is not there is left out, never reported as zero
    assert counters.read_data_metric(
        {"num": [{"counter": "prefetch.batches"}]}, run) is None
    assert counters.read_data_metric({"field": "trace.nothing"}, run) is None
    assert counters.read_data_metric({"field": "driver.x.y"}, run) is None
    assert counters.read_data_metric(
        {"num": [{"counter": "late.only"}],
         "den": [{"counter": "tune.searches"}]}, run) is None    # 0 / 0 change
    assert math.isclose(c.over("late.only", "window"), 2)
