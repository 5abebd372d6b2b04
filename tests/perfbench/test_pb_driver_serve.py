"""The open-loop driver once at a test-only size, by function call, and the
gate that keeps the measuring command off anything but the chip."""
import json
import subprocess
import sys
import types

import pytest

import pb_tiny
from perfbench import cells, run as runmod
from pb_tiny import check_line as _check_line, measure as _measure


def test_open_loop_driver(tmp_path):
    cell = pb_tiny.cell("tiny.serve", 1, pb_tiny.RESNET, pb_tiny.SERVE)
    line = _measure(cell, tmp_path, seconds=2.0)
    _check_line(line, cell, 2.0)
    rec = json.loads((tmp_path / "run_seed3_trace0.json").read_text())
    d = rec["driver"]
    assert d["attempted"] == line["attempted"] > 40     # ~80 due in 2 s
    assert d["req_p95_ms"] >= d["req_p50_ms"] > 0
    assert d["gen_late_p95_ms"] >= 0
    window = {k: rec["marks"]["window_end"][k] - rec["marks"]["window_start"][k]
              for k in rec["marks"]["window_end"]}
    assert window["serve.requests_total"] == window["serve.responses_ok"] \
        == d["attempted"]
    assert window["serve.shed_total"] == 0 and window["serve.errors"] == 0
    assert 1 <= window["serve.batches_total"] <= d["attempted"]
    assert window["jax.programs_built"] == 0    # the ladder was prewarmed
    assert d["check"]["ok"] and d["check"]["tolerance"] == 2e-4


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_only_the_cells_own_chips_pass_the_gate():
    tpu = _device("tpu", "TPU v5 lite")
    device, peaks = runmod.check_devices([tpu], 1)
    assert device == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.BenchError, match="no TPU"):
        runmod.check_devices([_device("cpu", "cpu")], 1)
    with pytest.raises(cells.BenchError, match="no published peaks"):
        runmod.check_devices([_device("tpu", "TPU v9 imaginary")], 1)
    with pytest.raises(cells.BenchError, match="asks for 4"):
        runmod.check_devices([tpu], 4)
    with pytest.raises(cells.BenchError, match="asks for 1"):
        runmod.check_devices([tpu] * 4, 1)


def test_the_command_prints_no_result_without_a_tpu(tmp_path):
    bench = cells.load_benchmark()
    cmd = [sys.executable if a == "python3" else a
           for a in bench["command"]]
    proc = subprocess.run(
        cmd + ["--workload", bench["workloads"][0]["name"], "--seed", "1",
               "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(ln.lstrip().startswith("{")
                   for ln in proc.stdout.splitlines())
