"""The program's host spans and set-up rows as the benchmark reads them
(perfbench/host_spans.py): nesting, self time and the attribution of idle gaps
on hand-made intervals; the union and self time of set-up rows; the reader and
the six metric files on a trace recorded on the chip (`make_hostspans1.py`,
beside this file) against the numbers that run wrote down; None where a trace
has no `mx:` span or a program no rows."""
import gzip
import importlib.util
import json
import os

import pytest

from perfbench import cells, host_spans, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPAN_METRICS = ("dispatch_host_ms_per_step", "exec_lookup_ms_per_step",
                "idle_in_dispatch_ms_per_step")
SETUP_METRICS = ("setup_trace_s", "setup_lower_s", "setup_import_s")

# one step, as TrainStep writes it: times in seconds
STEP = [("mx:train_step", 10.0, 20.0), ("mx:h2d", 10.5, 12.0),
        ("mx:rng", 12.0, 13.0), ("mx:compute", 13.5, 19.5),
        ("mx:exec_lookup", 14.0, 16.0), ("mx:launch", 16.5, 19.0),
        ("mx:input_wait", 21.0, 22.0)]


# -- intervals, by hand ---------------------------------------------------------

def test_nest_tiles_the_spans_by_their_deepest_name():
    pieces = host_spans.nest(STEP)
    assert pieces == [
        (("mx:train_step",), 10.0, 10.5),
        (("mx:train_step", "mx:h2d"), 10.5, 12.0),
        (("mx:train_step", "mx:rng"), 12.0, 13.0),
        (("mx:train_step",), 13.0, 13.5),
        (("mx:train_step", "mx:compute"), 13.5, 14.0),
        (("mx:train_step", "mx:compute", "mx:exec_lookup"), 14.0, 16.0),
        (("mx:train_step", "mx:compute"), 16.0, 16.5),
        (("mx:train_step", "mx:compute", "mx:launch"), 16.5, 19.0),
        (("mx:train_step", "mx:compute"), 19.0, 19.5),
        (("mx:train_step",), 19.5, 20.0),
        (("mx:input_wait",), 21.0, 22.0)]
    self_s = {}
    for path, s, e in pieces:
        self_s[path[-1]] = self_s.get(path[-1], 0.0) + e - s
    assert self_s == {"mx:train_step": 1.5, "mx:h2d": 1.5, "mx:rng": 1.0,
                      "mx:compute": 1.5, "mx:exec_lookup": 2.0,
                      "mx:launch": 2.5, "mx:input_wait": 1.0}


def test_nest_cuts_a_child_at_its_parents_end_and_takes_any_order():
    pieces = host_spans.nest([("b", 3.0, 9.0), ("a", 1.0, 5.0)])
    assert pieces == [(("a",), 1.0, 3.0), (("a", "b"), 3.0, 5.0)]
    assert host_spans.nest([]) == []
    # the same name inside itself: run_epoch's input_wait round the
    # prefetcher's own
    assert host_spans.nest([("w", 0.0, 4.0), ("w", 1.0, 3.0)]) == [
        (("w",), 0.0, 1.0), (("w", "w"), 1.0, 3.0), (("w",), 3.0, 4.0)]


def test_gaps_go_to_the_deepest_span_or_to_no_span():
    gaps = [(9.0, 10.25), (11.0, 11.5), (15.0, 17.0), (20.5, 21.5)]
    rows, unowned = host_spans.attribute(gaps, host_spans.nest(STEP))
    assert rows == pytest.approx({
        "mx:train_step": 0.25, "mx:h2d": 0.5, "mx:rng": 0.0,
        "mx:compute": 0.5, "mx:exec_lookup": 1.0, "mx:launch": 0.5,
        "mx:input_wait": 0.5})
    assert unowned == pytest.approx(1.0 + 0.5)      # 9-10 and 20.5-21


def _loaded(threads, ops, anchor=(8.0, 24.0)):
    """What `host_spans.load` returns; an event here may leave its stats
    out."""
    return {"anchor": anchor, "anchor_thread": "main", "ops": ops,
            "threads": {key: [(ev + ({},))[:4] for ev in events]
                        for key, events in threads.items()}}


def test_reduce_by_hand():
    step = [ev + ({"kind": "hit"},) if ev[0] == "mx:exec_lookup" else ev
            for ev in STEP]
    loaded = _loaded(
        {"main": step, "worker": [("mx:prefetch_place", 5.0, 9.0),
                                  ("mx:prefetch_place", 30.0, 31.0)]},
        # chip 1 is the idler one; an operation runs past the anchor's end
        {0: [(8.0, 23.0)],
         1: [(8.0, 9.0), (10.25, 11.0), (11.5, 15.0), (17.0, 20.5),
             (21.5, 30.0)]})
    got = host_spans.reduce(loaded)
    assert got["worst_chip"] == 1 and got["steps"] == 1
    assert got["window_s"] == 16.0
    assert got["idle_s"] == pytest.approx(1.25 + 0.5 + 2.0 + 1.0)
    assert got["idle_no_span_s"] == pytest.approx(1.5)
    # under mx:train_step and its children: all but input_wait's half second
    assert got["idle_in_step_s"] == pytest.approx(0.25 + 0.5 + 2.0)
    assert got["lookup_kinds"] == {"hit": 1}
    rows = {(r["thread"], r["span"]): r for r in got["spans"]}
    lookup = rows[("dispatch", "mx:exec_lookup")]
    assert (lookup["calls"], lookup["total_s"], lookup["self_s"]) == \
        (1, 2.0, 2.0)
    assert lookup["idle_s"] == pytest.approx(1.0)
    assert rows[("dispatch", "mx:compute")]["total_s"] == 6.0
    assert rows[("dispatch", "mx:compute")]["self_s"] == pytest.approx(1.5)
    # the worker's span is clipped to the anchor, counted, and owns no gap
    place = rows[("worker", "mx:prefetch_place")]
    assert (place["calls"], place["total_s"], place["idle_s"]) == \
        (1, 1.0, None)
    assert got["spans"][0]["span"] == "mx:train_step"
    table = host_spans.span_table(got)
    assert table[1].startswith("mx:train_step") and \
        host_spans.NO_SPAN in table[-2]


def test_reduce_without_spans_is_none_and_without_an_anchor_raises():
    ops = {0: [(8.0, 9.0)]}
    assert host_spans.reduce(_loaded({}, ops)) is None
    # spans outside the anchor are not spans of the slice
    assert host_spans.reduce(_loaded(
        {"main": [("mx:train_step", 1.0, 2.0)]}, ops)) is None
    only_worker = host_spans.reduce(_loaded(
        {"worker": [("mx:prefetch_place", 9.0, 10.0)]}, ops))
    assert only_worker["steps"] == 0
    assert only_worker["idle_no_span_s"] == only_worker["idle_s"] == 15.0
    with pytest.raises(ValueError):
        host_spans.reduce(_loaded({}, ops, anchor=None))
    with pytest.raises(ValueError):
        host_spans.reduce(_loaded({}, {}))


def test_setup_is_a_union_a_self_time_and_a_cut():
    rows = [("import", "incubator_mxnet_tpu", 1.0, 1.5),
            ("trace", "step", 10.0, 14.0), ("trace", "inner", 11.0, 12.0),
            ("trace", "inner", 12.5, 13.0), ("lower", "jit(step)", 14.0, 16.0),
            ("build", "jit(step)", 16.0, 17.0),
            ("exec_lookup", "disk:trainstep:sgd", 9.5, 17.5),
            ("trace", "late", 29.0, 31.0), ("build", "later", 40.0, 41.0)]
    got = host_spans.setup(rows, until=30.0, setup_s=25.0)
    assert got["rows"] == 7
    assert got["phases"] == pytest.approx({
        "import": 0.5, "trace": 4.0, "lower": 2.0, "build": 1.0,
        "exec_lookup": 8.0})
    top = {(ph, name): sec for ph, name, sec in got["top"]}
    assert top[("trace", "step")] == pytest.approx(2.5)     # 4 less 1.5
    assert top[("trace", "inner")] == pytest.approx(1.5)
    assert got["top"][0][:2] == ["exec_lookup", "disk:trainstep:sgd"]
    # every row once: the lookup holds its trace, lower and build
    assert got["named_s"] == pytest.approx(0.5 + 8.0)
    # the set-up began at 30 - 25 = 5, but a row began before it: what is
    # bare is bare INSIDE the set-up
    assert got["remainder_s"] == pytest.approx((9.5 - 5.0) + (30.0 - 17.5))
    assert got["holes"] == [pytest.approx([0.0, 4.5]),
                            pytest.approx([12.5, 25.0])]
    lines = host_spans.setup_table(got)
    assert lines[1].startswith("exec_lookup") and "17.000 s" in lines[-1]
    assert "0.0-4.5, 12.5-25.0" in lines[-1]
    bare = host_spans.setup([], until=30.0)
    assert bare["phases"] == {} and bare["holes"] == []


# -- the reader on a trace recorded on the chip ------------------------------------

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


@pytest.fixture(scope="module")
def record():
    with open(os.path.join(DATA, "hostspans1.json")) as f:
        return json.load(f)


def _run(unpacked, tag, setup_s=None, trace=None):
    unpacked(tag)
    cell = cells.Cell(name=tag, chips=1, config={}, traffic={},
                      end_to_end=[], per_layer=[], root=unpacked.root)
    return {"cell": cell, "peaks": {}, "trace": trace, "driver": {},
            "e2e": {} if setup_s is None else {"setup_s": setup_s},
            "counters": None}


@pytest.fixture
def fresh(monkeypatch):
    """No report kept from another test; no rows unless a test gives some."""
    monkeypatch.setattr(host_spans, "_seen", {})

    def rows(rows=None, born=1000.0):
        def program_table():
            if rows is None:
                raise AttributeError("module 'incubator_mxnet_tpu.profiler' "
                                     "has no attribute 'setup_stats'")
            return {"rows": [(ph, name, born + t0, born + t1)
                             for ph, name, t0, t1 in rows],
                    "kept": len(rows), "seen": len(rows), "short": 0}
        monkeypatch.setattr(host_spans, "program_table", program_table)
        monkeypatch.setattr(host_spans, "process_born", lambda: born)
    rows()
    return rows


def test_the_recorded_trace_reads_as_it_did_on_the_chip(unpacked, record,
                                                        fresh):
    assert record["device"]["platform"] == "tpu"
    got = host_spans.of(_run(unpacked, "hostspans1"))
    want = record["spans"]
    assert got["steps"] == want["steps"] == record["steps"]
    for key in ("window_s", "idle_s", "idle_no_span_s", "idle_in_step_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["lookup_kinds"] == want["lookup_kinds"] == \
        {"hit": record["steps"]}
    assert [(r["span"], r["calls"]) for r in got["spans"]] == \
        [(r["span"], r["calls"]) for r in want["spans"]]
    for mine, theirs in zip(got["spans"], want["spans"]):
        for key in ("total_s", "self_s", "idle_s"):
            assert mine[key] == pytest.approx(theirs[key], rel=1e-9), \
                (mine["span"], key)
    names = {r["span"] for r in got["spans"] if r["thread"] == "dispatch"}
    assert names == {"mx:train_step", "mx:h2d", "mx:rng", "mx:compute",
                     "mx:exec_lookup", "mx:launch", "mx:input_wait"}
    assert [r["thread"] != "dispatch" for r in got["spans"]
            if r["span"] == "mx:prefetch_place"] == [True]
    # it wrote its file where the cell's records go
    with open(os.path.join(unpacked.root, "perfbench", "out", "hostspans1",
                           "host_spans.json")) as f:
        assert json.load(f)["spans"]["steps"] == record["steps"]


def test_the_programs_spans_agree_with_the_benchmarks_own(unpacked, record,
                                                          fresh):
    """Two clocks, one answer: the idle time under mx:train_step (the
    trace's clock) against the idle time under the benchmark's `dispatch`
    span round the same call (its perf_counter, shifted by the anchor)."""
    summary = trace_reduce.reduce_trace(
        trace_reduce.load(unpacked("hostspans1")),
        spans=[tuple(r) for r in record["bench_spans"]],
        anchor_bench=tuple(record["anchor"]))
    bench = dict(summary["idle_gaps"])
    assert bench == pytest.approx(dict(record["bench_idle_gaps"]))
    got = host_spans.of(_run(unpacked, "hostspans1", trace=summary))
    assert got["idle_s"] == pytest.approx(
        summary["idle_share_worst"] * summary["window_s"])
    assert got["idle_in_step_s"] == pytest.approx(bench["dispatch"],
                                                  rel=0.05)
    # the rest is under the benchmark's `wait_input`, beyond the queue's
    # own wait (mx:input_wait): a ResNet cell reads 99% here, this toy 88%
    assert got["attributed_share"] == pytest.approx(
        (got["idle_s"] - got["idle_no_span_s"] + bench["read_loss"])
        / got["idle_s"])
    assert got["attributed_share"] > 0.85
    # mx:train_step sits just inside the benchmark's `dispatch`
    step = host_spans.span_seconds(got, host_spans.STEP, "total_s")
    dispatch = sum(b - a for name, a, b in record["bench_spans"]
                   if name == "dispatch")
    assert 0.9 * dispatch < step < dispatch


def test_the_recorded_rows_read_as_they_did_on_the_chip(unpacked, record,
                                                        fresh):
    fresh(record["rows"])
    got = host_spans.setup_of(_run(unpacked, "hostspans1",
                                   setup_s=record["setup_s"]))
    want = record["setup"]
    assert got["rows"] == want["rows"] > 100
    # the rows were rounded to the microsecond when they were written down
    assert got["phases"] == pytest.approx(want["phases"], abs=1e-3)
    assert got["named_s"] == pytest.approx(want["named_s"], abs=1e-3)
    assert [t[:2] for t in got["top"][:3]] == [t[:2] for t in want["top"][:3]]
    assert set(got["phases"]) >= {"import", "trace", "lower", "build",
                                  "train_step_init", "exec_lookup"}
    assert 0 < got["remainder_s"] < record["setup_s"]


@pytest.mark.parametrize("name", SPAN_METRICS + SETUP_METRICS)
def test_a_metric_file_reads_the_fixture(name, unpacked, record, fresh):
    fresh(record["rows"])
    run = _run(unpacked, "hostspans1", setup_s=record["setup_s"])
    value = cells.layer_metric_reader(name)(run)
    assert value == pytest.approx(record["read"][name], abs=1e-3)
    assert value > 0


@pytest.mark.parametrize("name", SPAN_METRICS + SETUP_METRICS)
def test_a_metric_file_is_silent_without_spans_or_rows(name, unpacked,
                                                       fresh):
    """The parent's program under these files: a trace with no `mx:` span
    (PR 24's fixture) and a profiler without the table."""
    run = _run(unpacked, "scoped1", setup_s=30.0)
    assert cells.layer_metric_reader(name)(run) is None
    assert host_spans.of(run) is None and host_spans.setup_of(run) is None


def test_nothing_to_read_is_none_and_never_raises(tmp_path, fresh):
    cell = cells.Cell(name="nothing", chips=1, config={}, traffic={},
                      end_to_end=[], per_layer=[], root=str(tmp_path))
    run = {"cell": cell, "peaks": {}, "trace": None, "driver": {},
           "e2e": {}, "counters": None}
    assert host_spans.of(run) is None and host_spans.setup_of(run) is None
    assert host_spans.per_step_ms(run, lambda r: 1.0) is None
    assert host_spans.setup_phase_s(run, "trace") is None


def test_a_phase_without_a_row_reads_zero_where_the_table_exists(unpacked,
                                                                 fresh):
    fresh([("import", "incubator_mxnet_tpu", 0.5, 0.6)])
    run = _run(unpacked, "scoped1", setup_s=30.0)
    assert host_spans.setup_phase_s(run, "import") == pytest.approx(0.1)
    assert host_spans.setup_phase_s(run, "lower") == 0.0


def test_benchmark_json_lists_the_six_as_the_issue_set_them_out():
    rows = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    resnet = ["resnet50.train", "resnet50.train-dp4"]
    for name in SETUP_METRICS:
        assert rows[name]["moves"] == "setup_s" and \
            "workloads" not in rows[name]
        assert rows[name]["source"] == "program_counter"
    for name in SPAN_METRICS:
        assert rows[name]["workloads"] == resnet
        assert rows[name]["moves"] == "train_items_per_s"
        assert rows[name]["source"] == "device_trace"
    assert rows["setup_import_s"]["layer"] == "frontend"
    for name in SPAN_METRICS + SETUP_METRICS:
        path = os.path.join(cells.ROOT, "perfbench", "layer_metrics",
                            name + ".py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.META == {k: rows[name][k] for k in (
            "layer", "moves", "unit", "better", "source")}
