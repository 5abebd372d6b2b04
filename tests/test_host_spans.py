"""The program's host spans on the trace's clock and its set-up rows
(profiler.span / profiler.setup_stats, PR 38): what a jax profiler session
recorded over a few tiny `TrainStep` calls holds, what the gate adds and what
it does not, and what the set-up table keeps."""
import glob
import itertools
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, profiler
from incubator_mxnet_tpu.io.prefetch import prefetch_to_device
from incubator_mxnet_tpu.parallel import TrainStep
from perfbench import host_spans

STEP_CHILDREN = {"mx:h2d", "mx:rng", "mx:compute"}


def _tiny_step():
    net = gluon.nn.Dense(4, in_units=19)
    net.initialize()
    step = TrainStep(net, lambda o, l: jnp.mean((o - l) ** 2),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05},
                     example_inputs=[mx.nd.ones((8, 19))])
    rs = np.random.RandomState(38)
    batches = [(rs.randn(8, 19).astype(np.float32),
                rs.randn(8, 4).astype(np.float32)) for _ in range(4)]
    step(*batches[0]).block_until_ready()       # compiled before any trace
    return step, batches[1:]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """`host_spans.load` of a session recorded over three steps fed by a
    prefetcher, with the gate off."""
    prev = profiler.attribution_enable(False)
    step, batches = _tiny_step()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    before = profiler.span_records()
    jax.profiler.start_trace(trace_dir)
    feed = prefetch_to_device(iter(batches), size=2)    # its worker starts
    try:                                                # inside the session
        with jax.profiler.TraceAnnotation(host_spans.ANCHOR):
            for batch in feed:
                loss = step(*batch)
            loss.block_until_ready()
    finally:
        jax.profiler.stop_trace()
        feed.close()
        profiler.attribution_enable(prev)
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return host_spans.load(path), profiler.span_records() - before


def test_trace_holds_three_steps_nested_on_one_thread(recorded):
    loaded, _ = recorded
    events = loaded["threads"][loaded["anchor_thread"]]
    assert sum(n == "mx:train_step" for n, *_ in events) == 3
    paths = {path for path, _, _ in host_spans.nest(
        [(n, s, e) for n, s, e, _ in events])}
    for child in STEP_CHILDREN:
        assert ("mx:train_step", child) in paths
    for leaf in ("mx:exec_lookup", "mx:launch"):
        assert ("mx:train_step", "mx:compute", leaf) in paths
    # nothing of a step escapes it, and the consumer's wait is beside it
    assert all(path[0] in ("mx:train_step", "mx:input_wait")
               for path in paths)
    assert ("mx:input_wait",) in paths


def test_lookup_carries_its_kind_and_the_worker_its_own_line(recorded):
    loaded, _ = recorded
    events = loaded["threads"][loaded["anchor_thread"]]
    kinds = [a.get("kind") for n, _, _, a in events
             if n == "mx:exec_lookup"]
    assert kinds == ["hit"] * 3
    others = {k: v for k, v in loaded["threads"].items()
              if k != loaded["anchor_thread"]}
    placed = [n for v in others.values() for n, *_ in v]
    assert placed.count("mx:prefetch_place") == 3
    assert "mx:prefetch_place" not in {n for n, *_ in events}


def test_gate_off_books_nothing_while_a_session_records(recorded):
    _, booked = recorded
    assert booked == 0
    prev = profiler.attribution_enable(False)
    try:
        profiler.dumps(reset=True)
        step, batches = _tiny_step()
        step.run_epoch(batches)
        assert profiler.span_records() == 0
        assert profiler.phase_stats()["phases"] == {}
    finally:
        profiler.attribution_enable(prev)


def test_gate_on_books_the_phases_it_booked_before():
    step, batches = _tiny_step()
    prev = profiler.attribution_enable(True)
    try:
        profiler.dumps(reset=True)
        step.run_epoch(batches)
        st = profiler.phase_stats()
        # the new spans (train_step, rng, exec_lookup, launch,
        # prefetch_place, the prefetcher's own input_wait) are trace-only
        assert set(st["phases"]) == {"input_wait", "h2d", "compute"}
        assert st["phases"]["h2d"]["count"] == 3
        assert st["phases"]["compute"]["count"] == 3
        assert st["phases"]["input_wait"]["count"] in (3, 4)
        assert st["steps"] == 3
        assert set(profiler.last_step_phases()) >= {"h2d", "compute"}
    finally:
        profiler.dumps(reset=True)
        profiler.attribution_enable(prev)


def test_span_is_a_no_op_an_annotation_or_a_booked_span(tmp_path):
    prev = profiler.attribution_enable(False)
    try:
        off = profiler.span("compute")
        assert off is profiler.span("h2d", book=False)
        with off as entered:
            entered.set_metadata(kind="hit")
        jax.profiler.start_trace(str(tmp_path))
        try:
            live = profiler.span("probe", args={"bucket": 8})
            assert isinstance(live, jax.profiler.TraceAnnotation)
            with live as entered:
                entered.set_metadata(kind="miss")
            profiler.attribution_enable(True)
            profiler.dumps(reset=True)
            with profiler.span("probe", book=False) as entered:
                assert isinstance(entered, jax.profiler.TraceAnnotation)
            assert profiler.span_records() == 0
            with profiler.span("probe") as entered:
                entered.set_metadata(kind="disk")
            assert profiler.phase_stats()["phases"]["probe"]["count"] == 1
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        probes = [a for events in host_spans.load(path)["threads"].values()
                  for n, _, _, a in events if n == "mx:probe"]
        assert [a.get("kind") for a in probes] == ["miss", None, "disk"]
        assert str(probes[0]["bucket"]) == "8"
    finally:
        profiler.dumps(reset=True)
        profiler.attribution_enable(prev)


def test_jitting_a_named_function_leaves_its_three_rows():
    def pr38_named_probe(x):
        for i in range(200):            # a trace of well over a millisecond
            x = jnp.tanh(x) * (1.0 + i)
        return x

    before = time.time()
    jax.jit(pr38_named_probe)(jnp.ones((7, 5))).block_until_ready()
    after = time.time()
    mine = [r for r in profiler.setup_stats()["rows"]
            if "pr38_named_probe" in r[1]]
    assert sorted(r[0] for r in mine) == ["build", "lower", "trace"]
    assert all(before <= t0 <= t1 <= after for _, _, t0, t1 in mine)
    order = {r[0]: r for r in mine}
    assert order["trace"][3] <= order["lower"][2] <= order["lower"][3] \
        <= order["build"][2]


def test_import_is_exactly_one_row_of_a_fresh_process():
    # this process's table may have been emptied by a dumps(reset=True)
    code = ("import time; t0 = time.time(); import incubator_mxnet_tpu as mx;"
            "import json; t1 = time.time();"
            "from incubator_mxnet_tpu import nd; nd.ones((2, 2)).asnumpy();"
            "print(json.dumps([t0, t1, mx.profiler.setup_stats()['rows']]))")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    t0, t1, rows = json.loads(out.stdout.strip().splitlines()[-1])
    imports = [r for r in rows if r[0] == "import"]
    assert len(imports) == 1 and imports[0][1] == "incubator_mxnet_tpu"
    assert t0 <= imports[0][2] < imports[0][3] <= t1
    assert rows[0] == imports[0] or rows[0][2] >= imports[0][2]
    # the eager op after it was traced, lowered and built
    assert {"trace", "lower", "build"} <= {r[0] for r in rows}


def test_a_train_step_leaves_its_own_rows():
    _tiny_step()
    rows = profiler.setup_stats()["rows"]
    assert any(r[0] == "train_step_init" and r[1] == "trainstep:sgd"
               for r in rows)
    # a compile through the program's own cache, under its key
    assert any(r[0] == "exec_lookup" and r[1].endswith(":trainstep:sgd")
               and r[1].split(":")[0] in ("miss", "disk") for r in rows)


@pytest.fixture
def empty_table(monkeypatch):
    monkeypatch.setattr(profiler, "_setup_rows", [])
    monkeypatch.setattr(profiler, "_setup_seq", itertools.count())
    monkeypatch.setattr(profiler, "_setup_short_seq", itertools.count())
    monkeypatch.setattr(profiler, "_setup_seen", [0, 0])


def test_table_stays_bounded_and_keeps_the_first_rows(empty_table,
                                                      monkeypatch):
    monkeypatch.setattr(profiler, "_SETUP_MAX", 5)
    for i in range(12):
        profiler.setup_row("trace", f"f{i}", i, i + 0.5)
    st = profiler.setup_stats()
    assert [r[1] for r in st["rows"]] == ["f0", "f1", "f2", "f3", "f4"]
    assert (st["kept"], st["seen"]) == (5, 12)


def test_jaxs_spans_under_a_millisecond_are_counted_not_kept(empty_table):
    event = "/jax/core/compile/jaxpr_trace_duration"
    profiler._on_jax_time_span(event, 5.0, 5.0004, fun_name="add")
    profiler._on_jax_time_span(event, 6.0, 6.0007, fun_name="bitwise_xor")
    profiler._on_jax_time_span(event, 7.0, 7.002, fun_name="step")
    profiler._on_jax_time_span("/jax/some/other_event", 8.0, 9.0)
    with profiler.setup_span("make_train_step", "probe"):   # the program's
        pass                                                # own: kept
    st = profiler.setup_stats()
    assert [(r[0], r[1]) for r in st["rows"]] == [
        ("trace", "step"), ("make_train_step", "probe")]
    assert (st["kept"], st["seen"], st["short"]) == (2, 2, 2)


def test_a_phase_is_the_union_and_a_name_its_self_time(empty_table):
    # an inner jit traced inside the step's trace is a row of its own
    profiler.setup_row("trace", "step", 10.0, 14.0)
    profiler.setup_row("trace", "inner", 11.0, 12.0)
    profiler.setup_row("trace", "inner", 12.5, 13.0)
    profiler.setup_row("trace", "other", 20.0, 21.0)
    profiler.setup_row("lower", "jit(step)", 14.0, 16.0)
    with profiler.setup_span("init_opt", "probe"):
        pass
    st = profiler.setup_stats()
    assert st["phases"]["trace"] == pytest.approx(5.0)      # not 6.5
    assert st["phases"]["lower"] == pytest.approx(2.0)
    top = {(ph, name): sec for ph, name, sec in st["top"]}
    assert top[("trace", "step")] == pytest.approx(2.5)
    assert top[("trace", "inner")] == pytest.approx(1.5)
    assert st["top"][0][:2] == ("trace", "step")
    # cut at an instant: the rows that ended by then
    cut = profiler.setup_stats(until=13.5)
    assert [r[1] for r in cut["rows"]] == ["inner", "inner"]
    assert cut["phases"] == {"trace": pytest.approx(1.5)}


def test_dumps_and_the_scrape_show_the_table(empty_table):
    profiler.setup_row("trace", "step", 1.0, 3.0)
    profiler.setup_row("import", "incubator_mxnet_tpu", 0.0, 0.5)
    table = profiler.dumps()
    assert "Set-up (phase; costliest names)" in table
    assert "  trace step" in table
    text = profiler.render_prometheus()
    assert 'mxnet_setup_phase_seconds{phase="trace"} 2.000000' in text
    assert ('mxnet_setup_name_seconds{phase="import",'
            'name="incubator_mxnet_tpu"} 0.500000') in text
    payload = json.loads(profiler.dumps(format="json", reset=True))
    assert payload["setup"]["phases"] == {"trace": 2.0, "import": 0.5}
    # reset means reset, for this family as for the others
    assert "setup" not in json.loads(profiler.dumps(format="json"))
    assert profiler.setup_stats()["seen"] == 0
