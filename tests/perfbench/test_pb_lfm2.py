"""What `lfm2-8b-a1b.train-4x8k-moe` adds to the benchmark: the configuration
against `cells.check_config` and the cuts it refuses, the program's layers the
family builds (and its refusal of a program without the "conv" mixer), the
required work it counts, the family through the `train` driver at a
test-only size with its routing and load counters, the three new readers on
a table of scopes, and every reader the cell is listed under returning a
number on the cell's configuration."""
import copy
import importlib.util
import json
import os

import pytest

import pb_tiny
from perfbench import cells, counters, op_scopes
from perfbench.families import lfm2_moe as family

CELL = "lfm2-8b-a1b.train-4x8k-moe"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("short_conv_time_share", "short_conv_roofline",
       "moe_load_max_over_mean")


def _row():
    return [c for c in cells.load_benchmark()["configs"]
            if c["name"] == "lfm2-8b-a1b"][0]


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
            config["vocab_size"]) == (6, 8, 16384)
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
    assert config["layer_pattern"] == {"period": 4, "leading_dense": 2}
    assert len(config["layer_types"]) == 24
    assert config["layer_types"][:6] == ["conv", "conv", "full_attention",
                                         "conv", "conv", "conv"]
    assert config["layer_types"].count("conv") == 18
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                  "num_experts_per_tok", "num_attention_heads",
                  "num_key_value_heads", "conv_L_cache", "num_dense_layers"):
        assert config[width] == config["published"][width], width
    assert config["experts_held"] == {"first": 0, "count": 8}
    mix = cell.traffic
    assert (mix["batch_per_chip"], mix["seq_len"]) == (4, 8192)
    assert mix["lr"] == 3e-4 and mix["ring"] == 4 and mix["remat"]
    # the uniform held share: 32,768 tokens x 4 slots x 8 / 32 experts
    assert mix["expert_rows"] > 4 * 8192 * 4 * 8 // 32
    assert mix["expert_rows"] <= 4 * 8192 * 4
    # the comparison compiles the timed program (its batch, its buffer); the
    # band reads the ring's first batch seen again, where a step that changed
    # nothing reads warm-up step 0's loss
    assert mix["check_items"] == mix["batch_per_chip"]
    band = mix["warmup_loss_band"]
    assert band["step"] == mix["ring"] < mix["warmup_steps"]
    assert cell.chips == 1 and len(row["source"]) <= 200
    for k in ("reduced_how", "deployment", "assumed", "departures",
              "parameters", "parameters_published", "bytes_per_parameter"):
        assert config[k], k
    assert {m["name"] for m in cell.per_layer} >= set(NEW)
    assert "moe_expert_roofline" not in {m["name"] for m in cell.per_layer}
    assert row["source"].startswith(
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert config["published"]["model_type"] == "lfm2_moe"


@pytest.mark.parametrize("key,value,why", [
    ("layer_types", ["conv", "conv", "full_attention", "conv", "conv",
                     "conv"], "not listed in `reduced`"),
    ("num_hidden_layers", 5, "under 4, or under one period"),
    ("num_experts", 7, "under 8"),
    ("num_experts", 16, None),
    ("vocab_size", 4096, "under one part in 8"),
    ("moe_intermediate_size", 896, "not listed in `reduced`"),
    ("num_experts_per_tok", 2, "not listed in `reduced`"),
    ("num_experts", 32, "nothing was cut"),
])
def test_a_deeper_cut_or_a_changed_width_is_refused(key, value, why):
    config = copy.deepcopy(cells.resolve(CELL).config)
    config[key] = value
    if why is None:             # 16 of 32 held: a cut the contract allows
        cells.check_config(_row(), config)
        return
    with pytest.raises(cells.BenchError, match=why):
        cells.check_config(_row(), config)


def test_the_programs_layers_are_what_the_configuration_says():
    config = copy.deepcopy(cells.resolve(CELL).config)
    mix = dict(dtype="bfloat16", remat=True, expert_rows=40960)
    mc = family.model_config(config, mix)
    assert mc.mixers == ("conv", "conv", "gqa", "conv", "conv", "conv")
    assert mc.mlps == ("dense", "dense") + ("experts",) * 4
    g = mc.gqa[2]
    assert (g.heads, g.window, g.qk_norm, g.gate) == (32, None, True, False)
    assert g.rotary.theta == 1e6 and g.rotary.share == 1.0 \
        and g.rotary.yarn is None
    ex = mc.experts
    assert (ex.count, ex.held, ex.per_token, ex.width, ex.shared_width,
            ex.score, ex.scaling, ex.norm_topk, ex.rows, ex.bias_rate) == (
        32, (0, 8), 4, 1792, 0, "sigmoid", 1.0, True, 40960, 1e-3)
    assert (mc.head_dim, mc.n_kv_heads, mc.d_ff, mc.norm_eps) == (
        64, 8, 7168, 1e-5)
    assert mc.tied_head and not mc.learned_positions
    for key, value in (("conv_L_cache", 4), ("conv_bias", True),
                       ("use_expert_bias", False),
                       ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            family.model_config(dict(config, **{key: value}), mix)
    other = dict(config, layer_types=["sliding_attention"] * 24)
    with pytest.raises(ValueError, match="sliding_attention"):
        family.model_config(other, mix)


def test_a_program_without_the_conv_mixer_is_refused_at_once(monkeypatch):
    """The parent commit's case: its TransformerLM has no "conv" mixer, and
    the family says so before it draws a weight."""
    from incubator_mxnet_tpu.models import transformer
    monkeypatch.setattr(transformer, "MIXERS",
                        ("mha", "sparse", "lightning", "gqa"))
    cell = cells.resolve(CELL)
    with pytest.raises(cells.BenchError, match="conv"):
        family.TrainJob(cell, 1, None)


def test_required_work_of_a_token():
    cell = cells.resolve(CELL)
    config = cell.config
    d = 2048
    conv, dense = 4 * d * d + 3 * d, 3 * d * 7168
    attention, router = 2 * d * d + 2 * d * 512, d * 32
    expert = 3 * d * 1792
    held = 16384 * d + 2 * (conv + dense) + attention + 3 * conv \
        + 4 * (router + 8 * expert)
    assert family.matmul_params(config) == held
    assert family.matmul_params(config, "expected") == \
        held - 4 * 7 * expert                   # 4 x 8 / 32 = one expert
    per_token = family.train_flops_per_item(config, cell.traffic)
    assert per_token == 6 * (held - 28 * expert) + 6 * 8192 * d
    assert per_token == pytest.approx(1.663e9, rel=1e-3)
    # the shares of the required FLOPs: conv, dense, experts, head
    share = lambda n: 6 * n / per_token
    assert share(5 * conv) == pytest.approx(0.303, abs=0.003)
    assert share(2 * dense) == pytest.approx(0.318, abs=0.003)
    assert share(4 * expert) == pytest.approx(0.159, abs=0.003)
    assert share(16384 * d) == pytest.approx(0.121, abs=0.003)
    sc = _metric("short_conv_roofline")
    n = 4 * 8192
    assert sc.conv_flops(n, d, 3) == 8 * n * d * d + 6 * n * d
    assert sc.conv_flops(n, d, 3, backward=True) == 2 * sc.conv_flops(n, d, 3)
    assert sc.conv_bytes(n, d, 3, 2) == 2 * (4 * d * d + 3 * d + 2 * n * d)
    assert sc.least_seconds(n, d, 3, 2, PEAKS) == pytest.approx(5583.3e-6,
                                                                rel=1e-4)
    assert sc.least_seconds(n, d, 3, 2, PEAKS, backward=True) == \
        pytest.approx(11166.6e-6, rel=1e-4)                  # FLOPs bind
    assert sc.conv_bytes(n, d, 3, 2) / 819e9 == pytest.approx(368.7e-6,
                                                              rel=1e-3)


# -- the family through the train driver, at a test-only size -----------------

def _tiny_cell():
    config = copy.deepcopy(cells.resolve(CELL).config)
    config.update(name="tiny-lfm2", hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  moe_intermediate_size=32, num_experts_per_tok=3,
                  num_experts=4, vocab_size=128,
                  experts_held={"first": 4, "count": 4})
    config["published"] = dict(config["published"], num_experts=16)
    traffic = dict(pb_tiny.TRAIN_LM, seq_len=64, expert_rows=192,
                   dtype="float32")
    return pb_tiny.cell("tiny.lfm2", 1, config, traffic, CELL)


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
    assert start["moe.steps"] == 3
    assert end["moe.steps"] == line["attempted"] + 3
    assert end["moe.slots_over"] == start["moe.slots_over"] == 0
    assert 0 < end["moe.held_slots"] < 192                  # a layer's mean
    # 4 x 64 tokens x 3 slots over 16 experts: a mean of 48 an expert
    assert 1.0 < end["moe.load_max_over_mean"] < 16.0
    assert all(4.0 < x < 6.5 for x in rec["driver"]["warmup_losses"])


# -- the readers on a table of scopes ----------------------------------------

def _rows():
    def row(words, seconds, category="convolution fusion", operands=(),
            results=(), calls=12, top="forward", **kw):
        return dict(dict(words=[top, "layer3"] + words, seconds=seconds,
                         calls=calls, backward=False, recomputed=False,
                         category=category, op="fusion",
                         operands=list(operands), results=list(results)),
                    **kw)
    big, vec = "bf16[4,8192,2048]", "f32[128,1,8192]"
    rows = [row(["attn", "norm"], 0.004),
            row(["attn", "short_conv"], 0.300),
            row(["attn", "short_conv", "short_conv_taps"], 0.050,
                "loop fusion"),
            row(["attn", "short_conv"], 0.150, backward=True,
                recomputed=True),
            row(["mlp", "moe", "moe_route"], 0.002),
            row(["mlp", "moe", "moe_dispatch"], 0.003),
            row(["mlp", "moe", "moe_experts"], 0.040, "custom-call"),
            row(["mlp", "moe", "moe_combine"], 0.005, backward=True),
            row(["attn", "flash_fwd"], 0.040, "custom-call", [big] * 3,
                [big, vec], calls=4),
            row(["attn", "flash_bwd_dq"], 0.060, "custom-call",
                [big] * 4 + [vec, big], [big] * 3, calls=4, backward=True),
            row(["logits"], 0.010), row(["loss"], 0.002, top="loss")]
    return {"window_s": 0.8, "chips": 1, "busy_s": 0.75, "rows": rows}


class _Counters:
    def __init__(self, end):
        self.c = counters.Counters()
        self.c.marks = {"window_start": {k: 0 for k in end},
                        "window_end": end}

    def over(self, *a):
        return self.c.over(*a)


def _run(cell, end=None):
    return {"cell": cell, "peaks": PEAKS, "e2e": {},
            "trace": {"top_op_share": 0.05, "idle_share_worst": 0.002},
            "driver": {"items_per_s": 40000.0, "flops_per_item": 1.663e9,
                       "step_p50_ms": 820.0},
            "counters": _Counters(end or {
                "moe.held_slots": 32768.0, "moe.slots_over": 0,
                "moe.expert_rows": 49152, "moe.load_max_over_mean": 1.4,
                "jax.programs_built": 0})}


def test_the_new_readers_on_a_table_of_scopes(monkeypatch):
    cell = cells.resolve(CELL)
    monkeypatch.setattr(op_scopes, "of", lambda run: _rows())
    read = lambda name, **kw: cells.layer_metric_reader(name)(_run(cell,
                                                                   **kw))
    assert read("short_conv_time_share") == pytest.approx(100 * 0.5 / 0.75)
    # 4 traced steps x 5 conv layers, forward and backward required
    least = 20 * (5583.32e-6 + 11166.64e-6)
    assert read("short_conv_roofline") == pytest.approx(100 * least / 0.5,
                                                        rel=1e-4)
    assert read("moe_load_max_over_mean") == pytest.approx(1.4)
    assert read("moe_load_max_over_mean",
                end={"moe.load_max_over_mean": 2.5}) == 2.5


@pytest.mark.parametrize("name", (
    "step_p50_ms", "compiles_in_window", "top_op_share", "device_idle_share",
    "model_flops_util", "unnamed_time_share", "remat_time_share",
    "loss_time_share", "flash_fwd_roofline", "flash_bwd_roofline",
    "gate_norm_time_share", "moe_time_share", "moe_dispatch_time_share",
    "moe_slots_over", "flash_kernels_time_share") + NEW)
def test_each_reader_the_cell_is_listed_under_reads_a_number(name,
                                                             monkeypatch):
    cell = cells.resolve(CELL)
    assert name in {m["name"] for m in cell.per_layer}
    monkeypatch.setattr(op_scopes, "of", lambda run: _rows())
    value = cells.layer_metric_reader(name)(_run(cell))
    assert isinstance(value, (int, float)), (name, value)
    if name.endswith("roofline"):
        assert 0 < value < 100, (name, value)


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
    other = cells.resolve("laguna-s-2.1.train-8k")      # no conv layer
    monkeypatch.setattr(op_scopes, "of", lambda run: _rows())
    if name == "short_conv_roofline":
        assert reader(_run(other)) is None
