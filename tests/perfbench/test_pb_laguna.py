"""What `laguna-s-2.1.train-8k` adds to the benchmark: the configuration
against `cells.check_config` and the cuts it refuses, the required work the
family and the new readers count (the windowed call's against a brute
count), the family through the `train` driver at a test-only size with its
routing counters, and the seven new readers on a table of scopes."""
import copy
import importlib.util
import json
import os

import numpy as np
import pytest

import pb_tiny
from perfbench import cells, counters, op_scopes
from perfbench.families import laguna as family

CELL = "laguna-s-2.1.train-8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("moe_time_share", "moe_dispatch_time_share", "moe_expert_roofline",
       "flash_win_fwd_roofline", "flash_win_bwd_roofline",
       "flash_kernels_time_share", "moe_slots_over")


def _row():
    return [c for c in cells.load_benchmark()["configs"]
            if c["name"] == "laguna-s-2.1"][0]


def _metric(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(
        cells.HERE, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_configuration_is_the_published_one_cut_three_ways():
    cell = cells.resolve(CELL)
    config, row = cell.config, _row()
    cells.check_config(row, config)
    assert config["reduced"] == row["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert list(config["cut"]) == ["depth", "experts held", "vocabulary"]
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == set(config["reduced"])
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 12544)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["layer_pattern"] == {"period": 4, "leading_dense": 1}
    assert config["layer_types"][:5] == ["full_attention"] + \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert config["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert config["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert config["layer_types"].count("sliding_attention") == 36
    for width in ("hidden_size", "intermediate_size", "head_dim",
                  "moe_intermediate_size", "shared_expert_intermediate_size",
                  "num_experts_per_tok", "sliding_window",
                  "num_key_value_heads"):
        assert config[width] == config["published"][width], width
    assert config["experts_held"] == {"first": 0, "count": 8}
    assert cell.traffic["batch_per_chip"] * cell.traffic["seq_len"] == 8192
    assert cell.traffic["expert_rows"] == 12288 and cell.traffic["lr"] == 3e-4
    assert cell.chips == 1 and len(row["source"]) <= 200
    for k in ("reduced_how", "deployment", "assumed", "departures",
              "parameters", "parameters_published", "bytes_per_parameter"):
        assert config[k], k
    assert {m["name"] for m in cell.per_layer} >= set(NEW)
    # the catalog's row, number for number (nested groups whole)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if "Laguna-S-2.1" in line]
        assert config["published"] == rows[0]["config"]
        assert row["source"].startswith(rows[0]["source_url"])


@pytest.mark.parametrize("key,value,why", [
    ("num_hidden_layers", 4, "under 4, or under one period"),
    ("num_experts", 4, "under 8"),
    ("num_experts", 12, "no divisor"),
    ("vocab_size", 12543, "under one part in 8"),
    ("moe_intermediate_size", 512, "not listed in `reduced`"),
    ("num_experts_per_tok", 2, "not listed in `reduced`"),
    ("sliding_window", 256, "not listed in `reduced`"),
    ("num_experts", 256, "nothing was cut"),
])
def test_a_deeper_cut_or_a_changed_width_is_refused(key, value, why):
    config = copy.deepcopy(cells.resolve(CELL).config)
    config[key] = value
    with pytest.raises(cells.BenchError, match=why):
        cells.check_config(_row(), config)


def test_the_programs_layers_are_what_the_configuration_says():
    config = copy.deepcopy(cells.resolve(CELL).config)
    mix = dict(dtype="bfloat16", remat=True, expert_rows=4096)
    mc = family.model_config(config, mix)
    assert mc.mixers == ("gqa",) * 5
    assert mc.mlps == ("dense", "experts", "experts", "experts", "experts")
    assert [(g.heads, g.window) for g in mc.gqa] == [
        (48, None), (72, 512), (72, 512), (72, 512), (48, None)]
    assert mc.gqa[1].rotary.yarn is None and mc.gqa[1].rotary.share == 1.0
    assert mc.gqa[1].rotary.theta == 10000.0
    assert mc.gqa[0].rotary.share == 0.5 and mc.gqa[0].rotary.yarn == (
        128, 8192, 32, 1, 1.4852030263919618)
    ex = mc.experts
    assert (ex.count, ex.held, ex.per_token, ex.width, ex.shared_width,
            ex.score, ex.scaling, ex.norm_topk, ex.rows) == (
        256, (0, 8), 10, 1024, 1024, "sigmoid", 2.5, True, 4096)
    assert (mc.head_dim, mc.n_kv_heads, mc.d_ff) == (128, 8, 12288)
    assert not mc.tied_head and not mc.learned_positions
    for key, value in (("gating", "per-layer"), ("attention_bias", True),
                       ("moe_router_logit_softcapping", 30.0),
                       ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match=key):
            family.model_config(dict(config, **{key: value}), mix)
    rope = copy.deepcopy(config)
    rope["rope_parameters"]["full_attention"]["rope_type"] = "llama3"
    with pytest.raises(ValueError, match="llama3"):
        family.model_config(rope, mix)
    other = dict(config, mlp_layer_types=["sparse"] * 48)
    with pytest.raises(ValueError, match="mlp_only_layers"):
        family.model_config(other, mix)


def test_required_work_of_a_token_of_a_windowed_call_and_of_the_experts():
    cell = cells.resolve(CELL)
    config = cell.config
    d, hd = 3072, 128
    attn = lambda h: 2 * d * h * hd + 2 * d * 8 * hd + d * h
    expert = 3 * d * 1024
    held = 12544 * d + attn(48) + 3 * d * 12288 + 3 * (
        attn(72) + d * 256 + 9 * expert) + attn(48) + d * 256 + 9 * expert
    assert family.matmul_params(config) == held == 772_448_256
    expected = held - 4 * (8 - 10 * 8 / 256) * expert
    assert family.matmul_params(config, "expected") == expected
    per_token = family.train_flops_per_item(config, cell.traffic)
    full, windowed = 6 * 8192 * 48 * hd, 12 * 512 * 72 * hd
    assert per_token == 6 * expected + 2 * full + 3 * windowed
    step = 8192 * per_token
    assert step == pytest.approx(30.04e12, rel=1e-3)
    assert 8192 * 2 * full == pytest.approx(4.95e12, rel=2e-3)
    assert 8192 * 3 * windowed == pytest.approx(1.39e12, rel=3e-3)
    # a windowed call's kept pairs against a brute count
    win = _metric("flash_win_fwd_roofline")
    for t, w in ((64, 16), (64, 64), (48, 100), (512, 1), (300, 7)):
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        assert win.kept_pairs(t, w) == int(((j <= i) & (i - j < w)).sum())
    assert win.kept_pairs(8192, 512) == 512 * 8192 - 512 * 511 // 2
    assert win.window_flops(1, 8192, 9216, 9216, 512) == \
        2 * 18432 * 4_063_488
    assert win.window_flops(1, 8192, 9216, 9216, 512, backward=True) == \
        2 * win.window_flops(1, 8192, 9216, 9216, 512)
    # a window as long as the sequence is the causal square op_scopes counts
    assert win.window_flops(4, 1024, 256, 256, 1024) == pytest.approx(
        op_scopes.flash_flops(4, 1024, 256), rel=2e-3)
    moe = _metric("moe_expert_roofline")
    assert moe.expert_flops(2560, 3072, 1024) == 6 * 2560 * 3072 * 1024
    assert moe.expert_flops(2560, 3072, 1024, backward=True) == \
        2 * moe.expert_flops(2560, 3072, 1024)
    assert moe.expert_bytes(2560, 3072, 1024, 8, 2) == \
        2 * (8 * 3 * 3072 * 1024 + 2 * 2560 * 3072)
    assert moe.least_seconds(2560, 3072, 1024, 8, 2, PEAKS) == \
        pytest.approx(245.3e-6, rel=1e-2)                   # FLOPs bind
    assert moe.least_seconds(2560, 3072, 1024, 8, 2, PEAKS,
                             backward=True) == pytest.approx(490.5e-6,
                                                             rel=1e-2)


# -- the family through the train driver, at a test-only size -----------------

def _tiny_cell():
    config = copy.deepcopy(cells.resolve(CELL).config)
    heads = [4 if h == 48 else 6
             for h in config["num_attention_heads_per_layer"]]
    config.update(name="tiny-laguna", hidden_size=64, intermediate_size=128,
                  head_dim=16, num_attention_heads=4, num_key_value_heads=2,
                  num_attention_heads_per_layer=heads, sliding_window=16,
                  moe_intermediate_size=32,
                  shared_expert_intermediate_size=32, num_experts_per_tok=3,
                  num_experts=4, vocab_size=128,
                  experts_held={"first": 4, "count": 4})
    config["published"] = dict(config["published"], num_experts=16)
    traffic = dict(pb_tiny.TRAIN_LM, seq_len=64, expert_rows=192,
                   dtype="float32")
    return pb_tiny.cell("tiny.laguna", 1, config, traffic, CELL)


def test_the_family_through_the_train_driver(tmp_path):
    cell = _tiny_cell()
    line = pb_tiny.measure(cell, tmp_path)
    pb_tiny.check_line(line, cell, 1.5)
    rec = json.loads((tmp_path / "run_seed3_trace0.json").read_text())
    check = rec["driver"]["check"]
    assert check["ok"] and check["limits"] == family.LIMITS
    assert 0 < check["relative_error"] < family.LIMITS["relative_error"]
    assert check["slots_differing"] <= family.LIMITS["slots_differing"]
    assert check["slots"] == 2 * 64 * 3 * 4
    assert rec["driver"]["flops_per_item"] == family.train_flops_per_item(
        cell.config, cell.traffic)
    start, end = rec["marks"]["window_start"], rec["marks"]["window_end"]
    assert end["jax.programs_built"] == start["jax.programs_built"]
    assert end["moe.expert_rows"] == 192
    assert start["moe.steps"] == 3 and end["moe.steps"] == line["attempted"] + 3
    assert end["moe.slots_over"] == start["moe.slots_over"] == 0
    assert 0 < end["moe.held_slots"] < 192                  # a layer's mean
    assert all(4.0 < x < 6.5 for x in rec["driver"]["warmup_losses"])


# -- the new readers on a table of scopes ---------------------------------------

def _rows():
    def row(words, seconds, category="convolution fusion", operands=(),
            results=(), calls=12, **kw):
        return dict(dict(words=["forward", "layer1"] + words, seconds=seconds,
                         calls=calls, backward=False, recomputed=False,
                         category=category, op="fusion",
                         operands=list(operands), results=list(results)),
                    **kw)
    big, vec = "bf16[1,8192,9216]", "f32[72,1,8192]"
    rows = [row(["mlp", "moe", "moe_route"], 0.002),
            row(["mlp", "moe", "moe_dispatch"], 0.003),
            row(["mlp", "moe", "moe_experts"], 0.030, "custom-call"),
            row(["mlp", "moe", "moe_shared"], 0.010),
            row(["mlp", "moe", "moe_combine"], 0.005, backward=True),
            row(["attn", "window_attn", "flash_win_fwd"], 0.0348,
                "custom-call", [big] * 3, [big, vec]),
            row(["attn", "window_attn", "flash_win_bwd_dq"], 0.030,
                "custom-call", [big] * 4 + [vec, big], [big, vec],
                backward=True),
            row(["attn", "window_attn", "flash_win_bwd_dkv"], 0.034,
                "custom-call", [big] * 4 + [vec, vec], [big, big],
                backward=True),
            row(["attn"], 0.0512)]
    return {"window_s": 0.21, "chips": 1, "busy_s": 0.2, "rows": rows}


class _Counters:
    def __init__(self, end):
        self.c = counters.Counters()
        self.c.marks = {"window_start": {k: 0 for k in end},
                        "window_end": end}

    def over(self, *a):
        return self.c.over(*a)


def _run(cell, end=None):
    return {"cell": cell, "peaks": PEAKS, "trace": None, "driver": {},
            "e2e": {}, "counters": _Counters(end or {
                "moe.held_slots": 2560.0, "moe.slots_over": 0,
                "moe.expert_rows": 4096})}


def test_the_new_readers_on_a_table_of_scopes(monkeypatch):
    cell = cells.resolve(CELL)
    monkeypatch.setattr(op_scopes, "of", lambda run: _rows())
    read = lambda name, **kw: cells.layer_metric_reader(name)(_run(cell,
                                                                   **kw))
    assert read("moe_time_share") == pytest.approx(25.0)
    assert read("moe_dispatch_time_share") == pytest.approx(5.0)
    # 4 traced steps x 4 expert layers, forward and backward required
    least = 16 * (245.27e-6 + 490.54e-6)
    assert read("moe_expert_roofline") == pytest.approx(100 * least / 0.030,
                                                        rel=1e-3)
    fwd = 2 * 18432 * 4_063_488 / 197e12             # 760 us: FLOPs bind
    assert read("flash_win_fwd_roofline") == pytest.approx(
        100 * 12 * fwd / 0.0348, rel=1e-3)
    assert read("flash_win_bwd_roofline") == pytest.approx(
        100 * 12 * 2 * fwd / 0.064, rel=1e-3)
    # the attention kernels alone: the grouped products' custom call is not
    assert read("flash_kernels_time_share") == pytest.approx(
        100 * (0.0348 + 0.030 + 0.034) / 0.2)
    for name in NEW[:6]:
        assert 0 < read(name) < 100, name
    assert read("moe_slots_over") == 0
    assert read("moe_slots_over", end={"moe.slots_over": 7}) == 7
    # held slots come from the step's counter: fewer slots, less required
    assert read("moe_expert_roofline", end={"moe.held_slots": 1280.0}) < \
        read("moe_expert_roofline")


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_reads_as_none(name, monkeypatch):
    """The parent's case (no trace to read, a program whose operations carry
    none of the words, a job with no such counter): no reader raises, none
    reports."""
    cell = cells.resolve(CELL)
    reader = cells.layer_metric_reader(name)
    monkeypatch.setattr(op_scopes, "of", lambda run: None)
    assert reader(_run(cell, end={"jax.programs_built": 3})) is None
    bare = _rows()
    for r in bare["rows"]:
        r["words"] = ["forward", "attn"]
    monkeypatch.setattr(op_scopes, "of", lambda run: bare)
    assert reader(_run(cell, end={"jax.programs_built": 3})) is None
    other = cells.resolve("gpt2-medium.train-1k")   # no window, no experts
    monkeypatch.setattr(op_scopes, "of", lambda run: _rows())
    if name.endswith("roofline"):
        assert reader(_run(other)) is None
