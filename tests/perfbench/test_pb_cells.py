"""BENCHMARK.json against its contract and against the files it names;
a cell, a configuration, a traffic mix and a data-defined per-layer metric
added by adding files and entries only."""
import importlib.util
import json
import os
import re
import shutil

import pytest

from perfbench import cells, counters

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")     # no spaces
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


def test_benchmark_json_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert bench["paths"] == ["perfbench", "tests/perfbench"]
    assert len(bench["command"]) <= 32 and not any(
        a.startswith("/") or ".." in a for a in bench["command"])
    assert isinstance(bench["run_seconds"], int) and \
        1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert 2 <= len(bench["workloads"]) <= 24
    assert all(len(x["why"]) <= 200
               for x in bench["configs"] + bench["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert c["file"].startswith("perfbench/") and c["reduced"] == []
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("lower",
                                                             "higher")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in \
        e2e["setup_s"]
    for m in bench["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "bound" not in m and LAYER.match(m["layer"]), m["layer"]


def test_every_cell_reports_what_the_contract_asks(bench):
    cell_names = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cell_names, m["name"]
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"])
        mine = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2 and cell.per_layer
        # a per-layer metric is reported only where the metric it moves is
        assert all(m["moves"] in mine for m in cell.per_layer), w["name"]


def test_every_cell_resolves_to_files_that_exist(bench):
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"])
        assert cell.chips == w["chips"]
        assert hasattr(cell.driver, "run")
        assert cell.traffic["driver"] == "train"
        assert hasattr(cell.family, "TrainJob"), w["name"]
        assert cell.config["reduced"] == [] and cell.config["source"]
        assert len(cell.traffic["why"]) > 20


def test_layer_metric_files_agree_with_benchmark_json(bench):
    folder = os.path.join(cells.HERE, "layer_metrics")
    on_disk = {os.path.splitext(f)[0] for f in os.listdir(folder)
               if f.endswith((".json", ".py"))}
    listed = {m["name"]: m for m in bench["per_layer"]}
    # the serving cell's four metrics wait, unlisted, for a cell that can
    # meet the memory floor (PERF.md section 7)
    assert on_disk - set(listed) == {
        "gen_late_p95_ms", "batch_occupancy", "rows_per_dispatch",
        "serve_device_idle_share"}
    assert set(listed) <= on_disk
    for name in sorted(on_disk):
        path = os.path.join(folder, name)
        m = listed.get(name)
        if os.path.isfile(path + ".json"):
            meta = cells.load_json(path + ".json")
            assert ("field" in meta) != ("num" in meta)
        else:
            spec = importlib.util.spec_from_file_location("m", path + ".py")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            meta = mod.META
        for key in ("layer", "moves", "unit", "better", "source"):
            assert meta[key] and (m is None or meta[key] == m[key]), \
                (name, key)
        assert LAYER.match(meta["layer"]), (name, meta["layer"])
        assert callable(cells.layer_metric_reader(name))


def test_unknown_names_are_refused(bench):
    with pytest.raises(cells.BenchError, match="no workload"):
        cells.resolve("resnet50.nothing")
    with pytest.raises(cells.BenchError, match="no file"):
        cells.layer_metric_reader("nothing_share")
    broken = json.loads(json.dumps(bench))
    broken["workloads"][0]["config"] = "resnet51"
    with pytest.raises(cells.BenchError, match="lacks"):
        cells.resolve(broken["workloads"][0]["name"], bench=broken)


def test_a_later_pr_adds_a_cell_as_data(tmp_path, bench):
    """Only new files, and appended entries in BENCHMARK.json: nothing that
    exists is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(cells.ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns(".cache", "out",
                                                  "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    config = cells.load_json(cells.ROOT + "/perfbench/configs/gpt2-medium.json")
    config.update(name="gpt2-medium-8k", max_len=8192,
                  assumed={"max_len": "8,192 learned positions"})
    mix = cells.load_json(cells.ROOT + "/perfbench/traffic/train-1k.json")
    mix.update(seq_len=8192, batch_per_chip=2, why="long sequences, so the "
               "flash kernels do a third of the required work")
    (root / "perfbench/configs/gpt2-medium-8k.json").write_text(
        json.dumps(config))
    (root / "perfbench/traffic/train-8k.json").write_text(json.dumps(mix))
    (root / "perfbench/layer_metrics/h2d_bytes_per_step.json").write_text(
        json.dumps({"layer": "input", "moves": "train_items_per_s",
                    "unit": "bytes", "better": "lower",
                    "source": "program_counter",
                    "num": [{"counter": "prefetch.h2d_bytes"}],
                    "den": [{"counter": "prefetch.batches"}]}))
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "gpt2-medium-8k", "source": "x",
                           "file": "perfbench/configs/gpt2-medium-8k.json",
                           "reduced": [], "why": "y"})
    new["workloads"].append({"name": "gpt2-medium-8k.train-8k",
                             "config": "gpt2-medium-8k",
                             "traffic": "train-8k", "chips": 1, "why": "z"})
    new["per_layer"].append({"name": "h2d_bytes_per_step", "unit": "bytes",
                             "better": "lower", "source": "program_counter",
                             "layer": "input", "moves": "train_items_per_s",
                             "workloads": ["gpt2-medium-8k.train-8k"]})
    for m in new["end_to_end"]:
        if m["name"] == "train_items_per_s":
            m["workloads"].append("gpt2-medium-8k.train-8k")
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    cell = cells.resolve("gpt2-medium-8k.train-8k", root=str(root))
    assert cell.config["max_len"] == 8192 and cell.traffic["seq_len"] == 8192
    assert cell.family.__name__ == "perfbench.families.transformer_lm"
    assert cell.driver.__name__ == "perfbench.drivers.train"
    assert {m["name"] for m in cell.end_to_end} == {"train_items_per_s",
                                                    "setup_s"}
    assert "h2d_bytes_per_step" in {m["name"] for m in cell.per_layer}
    c = counters.Counters()
    c.marks = {"window_start": {"prefetch.h2d_bytes": 100,
                                "prefetch.batches": 1},
               "window_end": {"prefetch.h2d_bytes": 900,
                              "prefetch.batches": 5}}
    read = cells.layer_metric_reader("h2d_bytes_per_step", root=str(root))
    assert read({"counters": c}) == 200
    # the old cells still resolve, and no file that existed was touched
    assert cells.resolve("resnet50.train", root=str(root)).chips == 1
    assert all(p.read_bytes() == data for p, data in before.items())
