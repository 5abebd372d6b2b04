"""The train driver once per family at a test-only size, by function call
(never through the measuring command), and the result line's schema."""
import json

import pytest

import pb_tiny
from pb_tiny import check_line as _check_line, measure as _measure


@pytest.mark.parametrize("chips", [1, 4], ids=["one-chip", "dp4"])
def test_resnet_train_driver(chips, tmp_path):
    like = "resnet50.train" if chips == 1 else "resnet50.train-dp4"
    cell = pb_tiny.cell(f"tiny.train{chips}", chips, pb_tiny.RESNET,
                        pb_tiny.TRAIN, like)
    line = _measure(cell, tmp_path)
    _check_line(line, cell, 1.5)
    rec = json.loads((tmp_path / "run_seed3_trace0.json").read_text())
    steps = rec["driver"]["steps"]
    assert line["attempted"] == steps
    # items are whole batches over all chips; the window ended with its
    # last step, at or a little after --seconds
    rate = line["metrics"]["train_items_per_s"]["value"]
    assert rate == pytest.approx(steps * 4 * chips / 1.5, rel=0.5)
    assert rate <= steps * 4 * chips / 1.5
    window = {k: rec["marks"]["window_end"][k] - rec["marks"]["window_start"][k]
              for k in rec["marks"]["window_end"]}
    assert window["jax.programs_built"] == 0            # warm-up covered it
    assert window["prefetch.batches"] == steps
    assert window["prefetch.h2d_bytes"] == steps * chips * 4 * (
        3 * 32 * 32 + 1) * 4
    if chips > 1:                       # pre-placed shards, XLA-only dispatch
        assert window["trainstep.preplaced_hits"] >= steps
        assert rec["marks"]["window_end"]["tune.withheld"] > 0
    assert rec["driver"]["check"]["ok"] and \
        rec["driver"]["check"]["relative_error"] > 0
    assert rec["driver"]["flops_per_item"] > 0


def test_lm_train_driver_and_a_loss_outside_its_band(tmp_path):
    cell = pb_tiny.cell("tiny.lm", 1, pb_tiny.LM, pb_tiny.TRAIN_LM,
                        "gpt2-medium.train-1k")
    line = _measure(cell, tmp_path)
    _check_line(line, cell, 1.5)
    assert line["metrics"]["train_items_per_s"]["value"] % 1 != 0  # unrounded
    cell.traffic["warmup_loss_band"] = dict(step=1, min=0.0, max=0.5)
    line = _measure(cell, tmp_path, seconds=0.3)
    assert line["correct"] is False and line["failed"] == 0
    rec = json.loads((tmp_path / "run_seed3_trace0.json").read_text())
    assert "outside the recorded band" in rec["problems"][0]


def test_a_traced_run_without_device_operations_is_refused(tmp_path):
    """On the CPU the profiler's trace has our anchor span and no device
    plane: the traced path runs to its end and the reducer refuses it."""
    cell = pb_tiny.cell("tiny.lm", 1, pb_tiny.LM, pb_tiny.TRAIN_LM,
                        "gpt2-medium.train-1k")
    with pytest.raises(ValueError, match="no device operations"):
        _measure(cell, tmp_path, trace=1, seconds=0.6)
    from perfbench import trace_reduce
    trace = trace_reduce.load(trace_reduce.find_xplane(
        str(tmp_path / "trace")))
    assert trace["ops"] == {} and trace["anchor"] is not None
    assert trace["anchor"][1] > trace["anchor"][0]
