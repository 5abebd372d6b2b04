"""What `minicpm-sala.train-8k` adds to the benchmark: the configuration
against `cells.check_config` and the cuts it refuses, the required work the
family and the new metric count, the family through the `train` driver at a
test-only size, and the three new readers on a table of scopes."""
import copy
import json

import pytest

import pb_tiny
from perfbench import cells, op_scopes
from perfbench.families import minicpm_sala as family

CELL = "minicpm-sala.train-8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _row():
    return [c for c in cells.load_benchmark()["configs"]
            if c["name"] == "minicpm-sala"][0]


def test_the_configuration_is_the_published_one_cut_in_depth_and_vocabulary():
    cell = cells.resolve(CELL)
    config, row = cell.config, _row()
    cells.check_config(row, config)
    assert config["reduced"] == row["reduced"] == ["num_hidden_layers",
                                                   "vocab_size"]
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 9181)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["mixer_types"][:4] == ["minicpm4"] + ["lightning-attn"] * 3
    assert config["mixer_types"].count("minicpm4") * 3 == \
        config["mixer_types"].count("lightning-attn") == 24
    assert cell.traffic["batch_per_chip"] * cell.traffic["seq_len"] == 8192
    assert cell.traffic["seq_len"] <= config["sparse"]["dense_len"]
    assert cell.chips == 1 and len(_row()["source"]) <= 200


@pytest.mark.parametrize("key,value,why", [
    ("num_hidden_layers", 3, "under 4, or under one period"),
    ("vocab_size", 9180, "under one part in 8"),
    ("head_dim", 64, "not listed in `reduced`"),
    ("intermediate_size", 8192, "not listed in `reduced`"),
    ("num_hidden_layers", 32, "nothing was cut"),
])
def test_a_deeper_cut_or_a_changed_width_is_refused(key, value, why):
    config = copy.deepcopy(cells.resolve(CELL).config)
    config[key] = value
    with pytest.raises(cells.BenchError, match=why):
        cells.check_config(_row(), config)


def test_a_width_listed_as_reduced_is_refused_too():
    config, row = copy.deepcopy(cells.resolve(CELL).config), _row()
    config["head_dim"] = 64
    config["reduced"] = row["reduced"] = row["reduced"] + ["head_dim"]
    config["reduced_how"]["head_dim"] = "128 -> 64"
    with pytest.raises(cells.BenchError, match="a width never"):
        cells.check_config(row, config)


def test_the_programs_mixers_are_what_the_switches_say():
    config = copy.deepcopy(cells.resolve(CELL).config)
    mc = family.model_config(config, dict(dtype="bfloat16", remat=True))
    assert mc.mixers == ("sparse", "lightning", "lightning", "lightning")
    assert (mc.n_heads, mc.n_kv_heads, mc.d_model // mc.n_heads) == \
        (32, 2, 128)
    assert mc.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert (mc.embed_scale, mc.logit_scale) == (12.0, 1 / 16)
    assert mc.select.dense_len == 8192 and mc.select.topk == 64
    assert not mc.tied_head and not mc.learned_positions
    config["attn_use_rope"] = True
    with pytest.raises(ValueError, match="attn_use_rope"):
        family.model_config(config, dict(dtype="bfloat16", remat=True))


def test_required_work_of_a_token_and_of_a_lightning_call():
    cell = cells.resolve(CELL)
    assert family.matmul_params(cell.config) == 1_146_998_784
    per_token = family.train_flops_per_item(cell.config, cell.traffic)
    assert per_token == 6 * 1_146_998_784 + 6 * 8192 * 4096 \
        + 3 * 3 * 4 * 32 * 128 * 128 == 7_102_193_664
    with pytest.raises(ValueError, match="dense_len"):
        family.train_flops_per_item(cell.config, dict(seq_len=16384))
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location("lr", os.path.join(
        cells.HERE, "layer_metrics", "lightning_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    shape = (1, 8192, 32, 128)
    assert mod.lightning_flops(*shape) == 4 * 8192 * 32 * 128 * 128
    assert mod.lightning_flops(*shape, backward=True) == \
        2 * mod.lightning_flops(*shape)
    assert mod.lightning_bytes(*shape, 2) == 4 * 8192 * 4096 * 2
    # HBM binds: 87 us of products against 328 us of bytes, forward
    assert mod.lightning_flops(*shape) / 197e12 == pytest.approx(87.2e-6,
                                                                 rel=1e-2)
    assert mod.least_seconds(*shape, 2, PEAKS) == pytest.approx(327.7e-6,
                                                                rel=1e-2)
    assert mod.least_seconds(*shape, 2, PEAKS, backward=True) == \
        pytest.approx(573.5e-6, rel=1e-2)


# -- the family through the train driver, at a test-only size -----------------

def _tiny_cell():
    config = copy.deepcopy(cells.resolve(CELL).config)
    config.update(name="tiny-sala", hidden_size=64, intermediate_size=128,
                  head_dim=16, lightning_head_dim=16, num_attention_heads=4,
                  lightning_nh=4, lightning_nkv=4, num_key_value_heads=2,
                  vocab_size=128, dim_model_base=16)
    traffic = dict(pb_tiny.TRAIN_LM, seq_len=64)
    return pb_tiny.cell("tiny.sala", 1, config, traffic, CELL)


def test_the_family_through_the_train_driver(tmp_path):
    cell = _tiny_cell()
    line = pb_tiny.measure(cell, tmp_path)
    pb_tiny.check_line(line, cell, 1.5)
    rec = json.loads((tmp_path / "run_seed3_trace0.json").read_text())
    check = rec["driver"]["check"]
    assert check["ok"] and 0 < check["relative_error"] < check["tolerance"]
    assert check["tolerance"] == family.TOLERANCE
    assert rec["driver"]["flops_per_item"] == family.train_flops_per_item(
        cell.config, cell.traffic)
    marks = rec["marks"]
    assert marks["window_end"]["jax.programs_built"] == \
        marks["window_start"]["jax.programs_built"]
    assert all(4.0 < x < 5.5 for x in rec["driver"]["warmup_losses"])  # ln 128


# -- the three readers on a table of scopes ------------------------------------

def _rows():
    def row(words, seconds, backward=False, recomputed=False):
        return dict(words=["forward", "layer1", "attn"] + words,
                    seconds=seconds, calls=12, backward=backward,
                    recomputed=recomputed, category="convolution fusion",
                    op="fusion", operands=[], results=[])
    rows = [row(["lightning", "lightning_intra"], 0.012),
            row(["lightning", "lightning_state"], 0.004, recomputed=True),
            row(["lightning", "lightning_intra"], 0.024, backward=True),
            row(["norm"], 0.003), row(["rope"], 0.002), row(["gate"], 0.001),
            row([], 0.054)]
    return {"window_s": 0.11, "chips": 1, "busy_s": 0.1, "rows": rows}


def _run(cell):
    return {"cell": cell, "peaks": PEAKS, "trace": None, "driver": {},
            "e2e": {}, "counters": None}


def test_the_new_readers_on_a_table_of_scopes(monkeypatch):
    cell = cells.resolve(CELL)
    monkeypatch.setattr(op_scopes, "of", lambda run: _rows())
    read = lambda name: cells.layer_metric_reader(name)(_run(cell))
    assert read("lightning_time_share") == pytest.approx(40.0)
    assert read("gate_norm_time_share") == pytest.approx(6.0)
    # 4 traced steps x 3 layers: 2 forward (one recomputed) and 1 backward
    least = 12 * (2 * 327.68e-6 + 573.44e-6)
    assert read("lightning_roofline") == pytest.approx(100 * least / 0.04,
                                                       rel=1e-3)
    assert 0 < read("lightning_roofline") < 100


@pytest.mark.parametrize("name", ["lightning_time_share",
                                  "lightning_roofline",
                                  "gate_norm_time_share"])
def test_a_program_without_the_scopes_reads_as_none(name, monkeypatch):
    """The parent's case (no trace to read, or a program whose operations
    carry none of the words): no reader raises, none reports."""
    cell = cells.resolve(CELL)
    reader = cells.layer_metric_reader(name)
    monkeypatch.setattr(op_scopes, "of", lambda run: None)
    assert reader(_run(cell)) is None
    bare = _rows()
    for r in bare["rows"]:
        r["words"] = ["forward", "attn"]
    monkeypatch.setattr(op_scopes, "of", lambda run: bare)
    assert reader(_run(cell)) is None
    other = cells.resolve("gpt2-medium.train-1k")      # no lightning layers
    monkeypatch.setattr(op_scopes, "of", lambda run: _rows())
    if name == "lightning_roofline":
        assert reader(_run(other)) is None
