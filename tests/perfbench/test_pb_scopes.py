"""The program's names read off a device trace (perfbench/op_scopes.py):
the walk of the protobuf wire on the two traces recorded on the chip by PR
22, the grammar of a path of names, the required work of a flash call
against a brute count, and the reduction and the seven readers on a trace
of a named toy recorded on the chip (`make_scoped1.py`, beside this file)."""
import gzip
import json
import os

import pytest

from perfbench import cells, op_scopes, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("flash_fwd_roofline", "flash_bwd_roofline", "remat_time_share",
           "loss_time_share", "bn_time_share", "bn_hbm_roofline",
           "unnamed_time_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def unpacked(tmp_path_factory):
    """tag -> a checkout-shaped directory whose `perfbench/out/<tag>/trace`
    holds the recorded trace, which is where a reader looks."""
    root = tmp_path_factory.mktemp("traces")

    def get(tag):
        folder = root / "perfbench" / "out" / tag / "trace" / "plugins" \
            / "profile" / "recorded"
        if not folder.exists():
            folder.mkdir(parents=True)
            with gzip.open(os.path.join(DATA, f"{tag}.xplane.pb.gz")) as f:
                (folder / f"{tag}.xplane.pb").write_bytes(f.read())
        return str(folder / f"{tag}.xplane.pb")
    get.root = str(root)
    return get


def _run(unpacked, tag):
    unpacked(tag)
    cell = cells.Cell(name=tag, chips=1, config={}, traffic={},
                      end_to_end=[], per_layer=[], root=unpacked.root)
    return {"cell": cell, "peaks": PEAKS, "trace": None, "driver": {},
            "e2e": {}, "counters": None}


# -- the wire, on PR 22's traces as they are ---------------------------------

@pytest.mark.parametrize("tag,chips", [("toy1", 1), ("toy4", 4)])
def test_walk_reads_the_metadata_profiledata_does_not_show(tag, chips,
                                                           unpacked):
    planes = op_scopes.walk(unpacked(tag))
    device = [p for p in planes
              if trace_reduce.DEVICE_PLANE.match(p["name"])]
    assert len(device) == chips
    # the same events jax's own reader sees, to the picosecond's rounding
    loaded = trace_reduce.load(unpacked(tag))
    for plane in device:
        chip = int(trace_reduce.DEVICE_PLANE.match(plane["name"]).group(1))
        line = [ln for ln in plane["lines"]
                if ln["name"] == trace_reduce.OP_LINE][0]
        assert len(line["events"]) == len(loaded["ops"][chip])
        mid, off, dur = line["events"][0]
        name, start, end = loaded["ops"][chip][0]
        assert trace_reduce.op_label(
            plane["event_metadata"][mid]["name"]) == name
        assert line["timestamp_ns"] * 1e-9 + off * 1e-12 == \
            pytest.approx(start, abs=2e-9)
        assert dur * 1e-12 == pytest.approx(end - start, abs=2e-9)
        conv = [m["stats"] for m in plane["event_metadata"].values()
                if m["stats"].get("hlo_category") == "convolution fusion"
                and m["name"].startswith("%convolution_tanh_fusion")]
        assert conv and all(
            s["tf_op"] == "jit(step)/dot_general:"
            and s["flops"] == 8_594_128_896
            and s["bytes_accessed"] == 16_777_216
            and s["source"].endswith(("toy_trace.py:19", "toy_trace.py:20"))
            for s in conv)
        reduces = [m for m in plane["event_metadata"].values()
                   if m["stats"].get("hlo_category") == "all-reduce"]
        assert bool(reduces) == (chips == 4)
        assert all(m["stats"]["tf_op"] == "jit(step)/bi,bj->ij/dot_general:"
                   for m in reduces)


@pytest.mark.parametrize("tag", ["toy1", "toy4"])
def test_reduce_agrees_with_the_first_reader_on_busy_time(tag, unpacked):
    reduced = op_scopes.reduce(unpacked(tag))
    with open(os.path.join(DATA, f"{tag}.json")) as f:
        was = json.load(f)["summary"]
    assert reduced["chips"] == was["chips"]
    assert reduced["window_s"] == pytest.approx(was["window_s"], rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(was["busy_s"], rel=1e-3)
    assert sum(r["seconds"] for r in reduced["rows"]) == \
        pytest.approx(reduced["busy_s"])
    by_label = {}
    for r in reduced["rows"]:
        by_label[r["op"]] = by_label.get(r["op"], 0.0) + r["seconds"]
    for label, seconds in was["op_seconds"].items():
        assert by_label[label] == pytest.approx(seconds, rel=1e-3, abs=1e-8)
    top = reduced["rows"][0]
    assert top["calls"] in (2, 3) and top["operands"] and top["results"]
    assert all(not r["words"] or r["words"] == ["bi,bj->ij"]
               for r in reduced["rows"])


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_names_reads_as_none(name, unpacked, capsys):
    """toy1 has scopes of jax's own (`bi,bj->ij`) and none of the three
    top words: the parent commit's case. No reader raises, none reports."""
    assert cells.layer_metric_reader(name)(_run(unpacked, "toy1")) is None


def test_a_missing_trace_reads_as_none(tmp_path, capsys):
    cell = cells.Cell(name="nothing", chips=1, config={}, traffic={},
                      end_to_end=[], per_layer=[], root=str(tmp_path))
    assert op_scopes.of({"cell": cell, "peaks": PEAKS}) is None
    assert "could not read" in capsys.readouterr().out


# -- the grammar -----------------------------------------------------------------

@pytest.mark.parametrize("tf_op,words,backward,recomputed,primitive", [
    ("jit(step)/jvp(forward)/layer1/mlp/tanh",
     ["forward", "layer1", "mlp"], False, False, "tanh"),
    ("jit(step)/transpose(jvp(forward))/layer0/jvp(forward)/layer0/"
     "checkpoint/attn/mul", ["forward", "layer0", "attn"], True, False,
     "mul"),
    ("jit(step)/transpose(jvp(forward))/layer0/jvp(forward)/layer0/"
     "checkpoint/rematted_computation/mlp/dot_general",
     ["forward", "layer0", "mlp"], True, True, "dot_general"),
    ("jit(step)/optimizer/mul:", ["optimizer"], False, False, "mul"),
    ("jit(trainstep)/jvp(forward)/BatchNorm/reduce_sum;"
     "jit(trainstep)/optimizer/mul", ["forward", "BatchNorm"], False, False,
     "reduce_sum"),
    ("jit(step)/jvp(forward)/layer0/mlp/jit(_var)/div",
     ["forward", "layer0", "mlp"], False, False, "div"),
    ("jit(step)/dot_general:", [], False, False, "dot_general"),
    ("", [], False, False, ""),
    ("jit(step)/jvp(forward)/layer3/attn/flash_fwd/pallas_call",
     ["forward", "layer3", "attn", "flash_fwd"], False, False,
     "pallas_call"),
    ("jit(step)/transpose(jvp(forward))/layer3/jvp(forward)/layer3/"
     "checkpoint/attn/flash_bwd_dkv/pallas_call",
     ["forward", "layer3", "attn", "flash_bwd_dkv"], True, False,
     "pallas_call"),
    ("jit(step)/jvp(loss)/reduce_max", ["loss"], False, False,
     "reduce_max"),
])
def test_a_path_of_names(tf_op, words, backward, recomputed, primitive):
    assert op_scopes.parse_path(tf_op) == {
        "words": words, "backward": backward, "recomputed": recomputed,
        "primitive": primitive}


def test_shapes_of_an_instruction():
    text = ("%flash_fwd.3 = (bf16[512,1024,64]{2,1,0:T(8,128)(2,1)}, "
            "f32[512,1024,1]{2,1,0}) custom-call(bf16[512,1024,64]{2,1,0} "
            "%a, bf16[512,64,1024]{2,1,0} %b, bf16[512,1024,64]{2,1,0} %c), "
            "custom_call_target=\"tpu_custom_call\", operand_layout_"
            "constraints={bf16[512,1024,64]{2,1,0}}")
    results, operands = op_scopes.shapes_of(text)
    assert results == ["bf16[512,1024,64]", "f32[512,1024,1]"]
    assert operands == ["bf16[512,1024,64]", "bf16[512,64,1024]",
                        "bf16[512,1024,64]"]
    assert op_scopes.shape_bytes("bf16[512,1024,64]") == 2 * 512 * 1024 * 64
    assert op_scopes.shape_bytes("f32[]") == 4
    assert op_scopes.shape_bytes("pred[8]") == 8
    assert op_scopes.shapes_of("no instruction at all") == ([], [])


# -- required work of a flash call ---------------------------------------------

@pytest.mark.parametrize("bh,t,d", [(3, 64, 16), (2, 128, 8)])
def test_flash_flops_against_a_brute_count(bh, t, d):
    """Every (query, key) pair the causal mask keeps, one product at a
    time: forward QK^T and PV; backward dV, dP, dQ, dK. The formula halves
    T^2 where the mask keeps T (T + 1) / 2 pairs: apart by 1 / T."""
    pairs = sum(1 for q in range(t) for k in range(t) if k <= q)
    dot = 2 * d                         # a multiply-add is two operations
    assert op_scopes.flash_flops(bh, t, d) == pytest.approx(
        bh * pairs * 2 * dot, rel=1.01 / t)
    assert op_scopes.flash_flops(bh, t, d, backward=True) == pytest.approx(
        bh * pairs * 4 * dot, rel=1.01 / t)
    assert op_scopes.flash_flops(bh, t, d) < bh * pairs * 2 * dot


# -- a named program, recorded on the chip (make_scoped1.py) -------------------

@pytest.fixture(scope="module")
def scoped(unpacked):
    with open(os.path.join(DATA, "scoped1.json")) as f:
        rec = json.load(f)
    run = _run(unpacked, "scoped1")
    run["peaks"] = rec["peaks"]
    return run, rec, op_scopes.reduce(unpacked("scoped1"))


def test_reduce_on_a_named_program(scoped, unpacked):
    run, rec, reduced = scoped
    assert rec["device"] == {"platform": "tpu", "kind": "TPU v5 lite"}
    rows = reduced["rows"]
    # the rows sum to the busy time the first reader found, to 0.1%
    assert sum(r["seconds"] for r in rows) == pytest.approx(
        rec["busy_s"], rel=1e-3)
    assert reduced["window_s"] == pytest.approx(rec["window_s"], rel=1e-6)
    assert {op_scopes.top_word(r) for r in rows} == {
        "forward", "loss", "optimizer", None}
    assert any(r["recomputed"] and r["backward"] for r in rows)
    assert any(r["backward"] and not r["recomputed"] for r in rows)
    assert any("BatchNorm" in r["words"] for r in rows)
    assert all(r["hbm_bytes"] <= r["bytes"] for r in rows)
    shape = "bf16[%d,%d,%d]" % tuple(rec["flash_shape"])
    for kernel, calls in (("flash_fwd", 4), ("flash_bwd_dq", 2),
                          ("flash_bwd_dkv", 2)):
        mine = [r for r in rows if kernel in r["words"]]
        # two layers; the forward kernel runs again in each one's remat
        assert len(mine) == calls, kernel
        assert all(r["category"] == "custom-call"
                   and r["primitive"] == "pallas_call"
                   and r["operands"][0] == shape
                   and r["op"].startswith(f"custom-call {kernel}")
                   for r in mine)
    assert sum(r["recomputed"] for r in rows if "flash_fwd" in r["words"]) \
        == 2
    # what `of` wrote beside the trace is the same reduction
    assert op_scopes.of(run)["busy_s"] == reduced["busy_s"]
    with open(os.path.join(unpacked.root, "perfbench", "out", "scoped1",
                           "scopes.json")) as f:
        assert len(json.load(f)["rows"]) == len(rows)
    lines = op_scopes.table(reduced)
    assert lines[1].startswith("forward") and any(
        s.startswith("  BatchNorm") for s in lines) and any(
        s.startswith("(unnamed)") for s in lines)


def _by_text(reduced, pattern):
    """Seconds of the rows whose raw `tf_op` matches: plain string tests,
    no parser."""
    import re
    return sum(r["seconds"] for r in reduced["rows"]
               if re.search(pattern, r["tf_op"]))


def _flash_by_hand(reduced, rec, backward):
    bh, t, d = rec["flash_shape"]
    qkv, lse = 2 * bh * t * d, 4 * bh * t
    if backward:        # q k v do out dq dk dv; lse dlse delta
        flops, moved = 4 * bh * t * t * d, 8 * qkv + 3 * lse
        seconds = _by_text(reduced, r"/flash_bwd_(dq|dkv)/")
        calls = sum(r["calls"] for r in reduced["rows"]
                    if "/flash_bwd_dq/" in r["tf_op"])
    else:               # q kt v out; lse
        flops, moved = 2 * bh * t * t * d, 4 * qkv + lse
        seconds = _by_text(reduced, r"/flash_fwd/")
        calls = sum(r["calls"] for r in reduced["rows"]
                    if "/flash_fwd/" in r["tf_op"])
    least = max(flops / rec["peaks"]["bf16_flops_per_s"],
                moved / rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * calls / seconds


TOPS = r"[/(](forward|loss|optimizer)[/)]"
BY_HAND = {
    "flash_fwd_roofline": lambda red, rec: _flash_by_hand(red, rec, False),
    "flash_bwd_roofline": lambda red, rec: _flash_by_hand(red, rec, True),
    "remat_time_share": lambda red, rec: 100 * _by_text(
        red, r"/rematted_computation/") / red["busy_s"],
    "loss_time_share": lambda red, rec: 100 * _by_text(
        red, r"[/(](logits|loss)[/)]") / red["busy_s"],
    "bn_time_share": lambda red, rec: 100 * _by_text(
        red, r"/BatchNorm/") / red["busy_s"],
    "bn_hbm_roofline": lambda red, rec: 100 * sum(
        r["hbm_bytes"] * r["calls"] for r in red["rows"]
        if "/BatchNorm/" in r["tf_op"]) / _by_text(red, r"/BatchNorm/")
        / rec["peaks"]["hbm_bytes_per_s"],
    "unnamed_time_share": lambda red, rec: 100 * (1 - _by_text(
        red, TOPS) / red["busy_s"]),
}


@pytest.mark.parametrize("name", READERS)
def test_a_reader_on_a_named_program(name, scoped, capsys):
    """Each reader gives the number the chip run itself read, and the one a
    plain string test over the raw paths gives."""
    run, rec, reduced = scoped
    got = cells.layer_metric_reader(name)(run)
    assert got == pytest.approx(rec["read"][name], rel=1e-9)
    assert got == pytest.approx(BY_HAND[name](reduced, rec), rel=1e-9)
    # (nothing but a program's arguments and results need be in HBM, so
    # the toy's BatchNorm may move no byte there)
    assert 0 < got < 100 or (name == "bn_hbm_roofline" and got == 0)


def test_flash_seconds_agree_with_the_older_flash_time_share(scoped):
    """`flash_time_share` reads the same kernels from outside (every custom
    call of the slice, by `trace_reduce`'s label): to 1%."""
    run, rec, reduced = scoped
    mine = 100 * _by_text(reduced, r"/flash_(fwd|bwd_dq|bwd_dkv)/") \
        / reduced["busy_s"]
    assert mine == pytest.approx(rec["read"]["flash_time_share"], rel=1e-2)
