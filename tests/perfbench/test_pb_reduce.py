"""The reduction from a device trace to numbers: interval arithmetic on a
hand-made schedule whose answers can be read off, then the same code on a
trace recorded on the chip."""
import gzip
import json
import os

import pytest

from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_interval_arithmetic():
    u = tr.union([(3, 4), (0, 1), (0.5, 2), (5, 5)])
    assert u == [(0, 2), (3, 4)] and tr.total(u) == 3
    assert tr.subtract([(0, 10)], u) == [(2, 3), (4, 10)]
    assert tr.subtract(u, [(0.5, 3.5)]) == [(0, 0.5), (3.5, 4)]
    assert tr.subtract(u, []) == u and tr.subtract([], u) == []
    assert tr.overlap(u, [(1, 3.25)]) == pytest.approx(1.25)


def test_self_time_takes_nested_operations_out_of_their_parent():
    events = [("while", 0, 10), ("conv", 1, 3), ("bn", 3, 4), ("conv", 5, 6),
              ("copy", 11, 12)]
    assert tr.self_seconds(events) == {"while": 6, "conv": 3, "bn": 1,
                                       "copy": 1}
    assert sum(tr.self_seconds(events).values()) == \
        tr.total(tr.union((s, e) for _, s, e in events))


def _schedule():
    """Two chips over a 10 s slice (trace clock 100..110). Chip 0: busy
    [100,102] fusion, [102,103] all-reduce alone, [104,106] fusion
    overlapped by an all-reduce [105,107] (1 s of it exposed), idle
    [103,104] and [107,110]. Chip 1: one fusion [100,109]."""
    return {"anchor": (100.0, 110.0), "lines": {}, "ops": {
        0: [("fusion.1", 99.5, 102.0), ("all-reduce.1", 102.0, 103.0),
            ("fusion.2", 104.0, 106.0), ("all-reduce.2", 105.0, 107.0),
            ("fusion.1", 111.0, 112.0)],
        1: [("fusion.1", 100.0, 109.0)]}}


def test_busy_idle_and_exposed_collectives_on_a_known_schedule():
    out = tr.reduce_trace(_schedule())
    assert out["window_s"] == 10 and out["chips"] == 2
    assert out["busy_s"] == pytest.approx((6 + 9) / 2)  # clipped to the slice
    assert out["idle_share_worst"] == pytest.approx(0.4)        # chip 0
    assert out["collective_s_worst"] == pytest.approx(3)
    assert out["collective_exposed_share_worst"] == pytest.approx(0.2)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx((2 + 9) / 2)]
    assert out["top_op_share"] == pytest.approx(11 / 15)     # of busy time
    assert "idle_gaps" not in out


def test_idle_gaps_are_named_by_the_benchmarks_own_spans():
    # the benchmark's clock runs 90 s behind the trace's: anchor at 10
    spans = [("dispatch", 12.9, 13.2), ("wait_input", 13.2, 14.5),
             ("read_loss", 17.0, 19.0)]
    out = tr.reduce_trace(_schedule(), spans=spans, anchor_bench=(10.0, 20.0))
    gaps = dict(out["idle_gaps"])
    # chip 0 idles [103,104] and [107,110], in benchmark time [13,14] and
    # [17,20]
    assert gaps["wait_input"] == pytest.approx(0.8)
    assert gaps["dispatch"] == pytest.approx(0.2)
    assert gaps["read_loss"] == pytest.approx(2.0)
    assert gaps[tr.NO_SPAN] == pytest.approx(1.0)
    assert sum(gaps.values()) == pytest.approx(4.0)
    assert out["idle_gaps"][0][0] == "read_loss"        # longest first


def test_a_trace_with_nothing_to_read_is_an_error_not_a_zero():
    empty = dict(_schedule(), ops={})
    with pytest.raises(ValueError, match="no device operations"):
        tr.reduce_trace(empty)
    with pytest.raises(ValueError, match="no anchor"):
        tr.reduce_trace(dict(_schedule(), anchor=None))
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(DATA + "/nothing-here")


# -- the same code on traces recorded on the chip (PR 22, TPU v5e) ----------
# toy<n>: three steps of a small jitted program on n chips (two matmuls and
# a product summed over the sharded batch, so an all-reduce where n > 1),
# the host sleeping 20 ms between steps inside a "wait_input" span.

def _recorded(tag, tmp_path):
    path = tmp_path / f"{tag}.xplane.pb"
    with gzip.open(os.path.join(DATA, f"{tag}.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with open(os.path.join(DATA, f"{tag}.json")) as f:
        return tr.load(str(path)), json.load(f)


def _brute_busy(events, t0, t1, step=1e-6):
    """Busy seconds by painting a microsecond grid: no interval logic."""
    import numpy as np
    grid = np.zeros(int((t1 - t0) / step) + 1, bool)
    for _, s, e in events:
        a, b = (max(s, t0) - t0) / step, (min(e, t1) - t0) / step
        if b > a:
            grid[int(round(a)):int(round(b))] = True
    return float(grid.sum()) * step


@pytest.mark.parametrize("tag,chips", [("toy1", 1), ("toy4", 4)])
def test_reducer_on_a_recorded_trace(tag, chips, tmp_path):
    trace, rec = _recorded(tag, tmp_path)
    assert sorted(trace["ops"]) == list(range(chips))
    assert trace["anchor"] is not None
    assert trace["lines"]["/device:TPU:0"][tr.OP_LINE] > 0
    out = tr.reduce_trace(trace, spans=[tuple(r) for r in rec["spans"]],
                          anchor_bench=tuple(rec["anchor"]))
    was = rec["summary"]                # what the chip run itself reduced
    for key in ("window_s", "busy_s", "idle_share_worst", "top_op_share",
                "collective_s_worst", "collective_exposed_share_worst"):
        assert out[key] == pytest.approx(was[key], rel=1e-9), key
    t0, t1 = trace["anchor"]
    brute = [_brute_busy(ev, t0, t1) for ev in trace["ops"].values()]
    assert out["busy_s"] == pytest.approx(sum(brute) / chips, abs=2e-5)
    assert out["idle_share_worst"] == pytest.approx(
        1 - min(brute) / (t1 - t0), abs=1e-3)
    assert 0 < out["busy_s"] < out["window_s"]
    # the host slept 3 x 20 ms while the device had nothing to do
    gaps = dict(out["idle_gaps"])
    assert gaps["wait_input"] >= 0.055
    assert sum(gaps.values()) == pytest.approx(
        out["idle_share_worst"] * out["window_s"], rel=1e-6)
    labels = [name for name, _ in out["device_ops"]]
    assert all(len(name) <= 100 and " " in name for name in labels)
    assert any(name.startswith("fusion ") or name.startswith("convolution ")
               for name in labels)
    if chips == 1:
        assert out["collective_s_worst"] == 0
    else:
        assert out["collective_s_worst"] > 0
        assert 0 < out["collective_exposed_share_worst"] < 1
        assert any(tr.COLLECTIVE.match(k) for k in out["op_seconds"])
