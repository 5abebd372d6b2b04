"""What the host did while the chip idled, and where a start's seconds go:
the program's own spans and set-up rows, read from outside the program.

`trace_reduce.py` reads WHEN the device worked and puts its idle gaps under
the BENCHMARK's spans, which sit round the benchmark's calls into the
program and cannot see beneath them. Since PR 38 the program writes its own
spans into whatever jax profiler session records (`profiler.span`: a
`jax.profiler.TraceAnnotation` named `mx:<phase>` on its thread's line of
the host plane, on the clock of the device's "XLA Ops" lines) and keeps a
table of set-up rows. This module reads both; the functions at the end are
what the metric files in `layer_metrics/` call.

**Spans** (the names are the contract with the program, as `forward`, `loss`
and `optimizer` are for `op_scopes.py`), parent to child on the thread that
dispatches steps, which is the thread that holds the benchmark's anchor:

    mx:train_step        TrainStep.__call__, whole
      mx:h2d             the eager dtype cast of the batch, any device_put
      mx:rng             the eager key split
      mx:compute         round the jitted step
        mx:exec_lookup   _CachedJit: signature, memo, memory tier, two locks;
                         stat `kind` = hit | disk | miss
        mx:launch        the loaded executable called
    mx:input_wait        DevicePrefetcher.__next__: the consumer's wait
    mx:prefetch_place    the prefetcher's worker thread (its own line)

`reduce()` clips every `mx:` event to the anchor, nests them per thread, gives
each span its self time (duration less what its children cover), recomputes
the worst chip's idle gaps with `trace_reduce`'s interval functions and puts
every gap under the DEEPEST `mx:` span of the dispatching thread open at that
instant, else under `(no program span)`. A trace without `mx:` spans (the
parent commit, a language-model cell, whose step is a bare `jax.jit`): None,
one line in the log, and the three metrics that read spans are left out.

**Set-up rows** `(phase, name, t0, t1)`, seconds on `time.time()`, from
`incubator_mxnet_tpu.profiler.setup_stats()`: `trace`, `lower`, `build` (jax's
own time spans for tracing a function, turning its jaxpr into MLIR, compiling
or loading it; `name` the function's), `import` (the package's `__init__`,
once), `train_step_init`, `make_train_step`, `shard_params`, `init_opt`,
`exec_lookup` (`<disk|miss>:<key>`: a load or a compile through the program's
executable cache); of jax's spans only those of a millisecond or more are
rows. `setup()` keeps the rows that END before the window began:
`e2e.setup_s` after the process's birth, which `run.process_age_s` puts on the
same wall clock. jax reports a `jit` traced inside another function's trace
as a row of its own, so a phase's seconds are the UNION of its rows'
intervals and a name's are its rows' self time, never plain sums. A program
without the table: None, one line, the three set-up metrics left out.

Both parts go to the log as tables and into
`perfbench/out/<cell>/host_spans.json`. Nothing here raises into a run.
"""
from __future__ import annotations

import json
import os
import time
import traceback

from .spans import ANCHOR
from .trace_reduce import (DEVICE_PLANE, OP_LINE, find_xplane, overlap,
                           self_seconds, subtract, total, union)

PREFIX = "mx:"
STEP = "mx:train_step"
NO_SPAN = "(no program span)"


# -- the trace ------------------------------------------------------------------

def load(path):
    """{"anchor": (start_s, end_s) or None, "anchor_thread": key,
    "threads": {key: [(name, start_s, end_s, {stat: value})]} of the `mx:`
    events of each host thread, "ops": {chip: [(start_s, end_s)]}} from one
    .xplane.pb. A thread's key is "<plane>/<line>#<index>": two threads may
    bear one name."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"anchor": None, "anchor_thread": None, "threads": {}, "ops": {}}
    for plane in data.planes:
        chip = DEVICE_PLANE.match(plane.name)
        for i, line in enumerate(plane.lines):
            if chip:
                if line.name == OP_LINE:
                    out["ops"][int(chip.group(1))] = [
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
                continue
            key, mine = f"{plane.name}/{line.name}#{i}", []
            for e in line.events:
                name = e.name
                if name == ANCHOR:
                    out["anchor"] = (e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9)
                    out["anchor_thread"] = key
                elif name.startswith(PREFIX):
                    mine.append((name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9,
                                 {k: v for k, v in e.stats}))
            if mine:
                out["threads"][key] = mine
    return out


# -- intervals ------------------------------------------------------------------

def nest(events):
    """One thread's (name, start, end) events as pieces [(path, start, end)]
    that tile the events' union: `path` is the tuple of names open over the
    piece, outermost first, so path[-1] is the DEEPEST span there and a
    span's self time is the length of the pieces it ends. A child never
    outlasts its parent."""
    out, stack = [], []                 # stack of [name, end, covered up to]

    def piece(until):
        top = stack[-1]
        if until > top[2]:
            out.append((tuple(s[0] for s in stack), top[2], until))
            top[2] = until

    def close(until):
        while stack and stack[-1][1] <= until:
            piece(stack[-1][1])
            end = stack.pop()[1]
            if stack:
                stack[-1][2] = max(stack[-1][2], end)
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            e = min(e, stack[-1][1])
            piece(s)
        if e > s:
            stack.append([name, e, s])
    close(float("inf"))
    return out


def attribute(gaps, pieces):
    """{deepest span: seconds of `gaps` under it} and the seconds under no
    span, for a union `gaps` and the pieces of `nest`."""
    by_name = {}
    for path, s, e in pieces:
        by_name.setdefault(path[-1], []).append((s, e))
    rows = {name: overlap(gaps, union(iv)) for name, iv in by_name.items()}
    covered = union((s, e) for _, s, e in pieces)
    return rows, total(subtract(gaps, covered))


def reduce(loaded):
    """The table of the traced slice, or None where it holds no `mx:` span.
    {"window_s", "steps" (mx:train_step calls), "worst_chip", "idle_s" (the
    worst chip's), "idle_no_span_s", "idle_in_step_s" (under mx:train_step
    and its children), "lookup_kinds", "spans": [{"span", "thread":
    "dispatch" | <key>, "calls", "total_s", "self_s", "idle_s" (dispatching
    thread only)}]}."""
    if loaded["anchor"] is None:
        raise ValueError(f"the trace holds no anchor span {ANCHOR!r}")
    if not loaded["ops"]:
        raise ValueError("the trace holds no device operations")
    t0, t1 = loaded["anchor"]
    threads = {}
    for key, events in loaded["threads"].items():
        inside = [(n, max(s, t0), min(e, t1), a) for n, s, e, a in events
                  if min(e, t1) > max(s, t0)]
        if inside:
            threads[key] = inside
    if not threads:
        return None
    gaps_of = {chip: subtract([(t0, t1)], union(
        (max(s, t0), min(e, t1)) for s, e in events))
        for chip, events in loaded["ops"].items()}
    worst = max(gaps_of, key=lambda c: total(gaps_of[c]))
    gaps = gaps_of[worst]
    spans, kinds = [], {}
    idle_no_span = idle_in_step = steps = 0
    for key, events in sorted(threads.items()):
        dispatching = key == loaded["anchor_thread"]
        pieces = nest([(n, s, e) for n, s, e, _ in events])
        idle = {}
        if dispatching:
            idle, idle_no_span = attribute(gaps, pieces)
            idle_in_step = overlap(gaps, union(
                (s, e) for path, s, e in pieces if STEP in path))
            steps = sum(n == STEP for n, _, _, _ in events)
        for name in sorted({n for n, _, _, _ in events}):
            mine = [(s, e) for n, s, e, _ in events if n == name]
            spans.append({
                "span": name, "thread": "dispatch" if dispatching else key,
                "calls": len(mine), "total_s": total(union(mine)),
                "self_s": sum(e - s for path, s, e in pieces
                              if path[-1] == name),
                "idle_s": idle.get(name) if dispatching else None})
        for n, _, _, args in events:
            if n == PREFIX + "exec_lookup":
                kind = str(args.get("kind", "?"))
                kinds[kind] = kinds.get(kind, 0) + 1
    if loaded["anchor_thread"] not in threads:  # spans on other threads only
        idle_no_span = total(gaps)
    spans.sort(key=lambda r: (r["thread"] != "dispatch", -r["total_s"]))
    return {"window_s": t1 - t0, "steps": steps, "worst_chip": worst,
            "idle_s": total(gaps), "idle_no_span_s": idle_no_span,
            "idle_in_step_s": idle_in_step, "lookup_kinds": kinds,
            "spans": spans}


def span_table(reduced):
    steps = reduced["steps"] or 1
    lines = [f"{'span (ms a step)':<26}{'thread':>10}{'calls':>7}"
             f"{'total':>10}{'self':>10}{'idle under':>12}"]
    for r in reduced["spans"]:
        idle = "" if r["idle_s"] is None else f"{1e3 * r['idle_s'] / steps:.4f}"
        lines.append(
            f"{r['span']:<26}{r['thread'][-10:]:>10}{r['calls']:>7}"
            f"{1e3 * r['total_s'] / steps:>10.4f}"
            f"{1e3 * r['self_s'] / steps:>10.4f}{idle:>12}")
    lines.append(f"{NO_SPAN:<26}{'':>10}{'':>7}{'':>10}{'':>10}"
                 f"{1e3 * reduced['idle_no_span_s'] / steps:>12.4f}")
    lines.append(
        f"{reduced['steps']} steps in {reduced['window_s']:.4f} s; chip "
        f"{reduced['worst_chip']} idle {1e3 * reduced['idle_s']:.3f} ms, "
        f"{1e3 * reduced['idle_in_step_s']:.3f} of them under {STEP} and "
        f"its children; lookups {reduced['lookup_kinds']}")
    return lines


# -- set-up -----------------------------------------------------------------------

def program_table():
    """The program's set-up table ({"rows", "kept", "seen", "short"});
    AttributeError from a program that keeps none (the parent of the PR
    that added it)."""
    from incubator_mxnet_tpu import profiler
    return profiler.setup_stats()


def process_born():
    """time.time() of this process's birth; `e2e.setup_s` later its window
    began."""
    from .run import process_age_s
    return time.time() - process_age_s()


def setup(rows, until, setup_s=None, top=10, holes=6):
    """{"phases": {phase: seconds}, "top": [[phase, name, self seconds]],
    "rows", "named_s" (the union of every row), "setup_s", "remainder_s",
    "holes"} of the rows that ended by `until`. `holes`, where `setup_s`
    is given: the longest stretches of the set-up under no row, as [from,
    to] in seconds after the process's birth (`until - setup_s`), the first
    of them the interpreter's and jax's own start before the package's
    import: where the remainder is."""
    rows = [r for r in rows if r[3] <= until]
    phases, by_name = {}, {}
    for phase in sorted({r[0] for r in rows}):
        mine = [(name, t0, t1) for ph, name, t0, t1 in rows if ph == phase]
        phases[phase] = total(union((t0, t1) for _, t0, t1 in mine))
        for name, sec in self_seconds(mine).items():
            by_name[(phase, name)] = sec
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    covered = union((t0, t1) for _, _, t0, t1 in rows)
    out = {"phases": phases, "rows": len(rows), "named_s": total(covered),
           "top": [[ph, name, sec] for (ph, name), sec in ranked[:top]],
           "setup_s": setup_s, "remainder_s": None, "holes": []}
    if setup_s is not None:
        born = until - setup_s
        bare = subtract([(born, until)], covered)
        out["remainder_s"] = total(bare)
        out["holes"] = sorted(
            ([s - born, e - born] for s, e in sorted(
                bare, key=lambda iv: iv[0] - iv[1])[:holes]))
    return out


def setup_table(found):
    lines = [f"{'set-up phase / costliest names':<64}{'seconds':>10}"]
    for ph, sec in sorted(found["phases"].items(), key=lambda kv: -kv[1]):
        lines.append(f"{ph:<64}{sec:>10.3f}")
    for ph, name, sec in found["top"]:
        lines.append(f"{'  ' + ph + ' ' + name[:56]:<64}{sec:>10.3f}")
    if found["setup_s"] is not None:
        holes = ", ".join(f"{a:.1f}-{b:.1f}" for a, b in found["holes"])
        lines.append(
            f"{found['rows']} rows before the window name "
            f"{found['named_s']:.3f} s of setup_s {found['setup_s']:.3f}: "
            f"{found['remainder_s']:.3f} s belong to no row; the longest "
            f"such stretches, seconds after the process began: {holes}")
    return lines


# -- what the per-layer metric files call -----------------------------------------

_seen = {}          # (checkout, cell) -> the report: six readers, one read


def _out_dir(run):
    return os.path.join(run["cell"].root, "perfbench", "out",
                        run["cell"].name)


def _spans_part(run):
    try:
        path = find_xplane(os.path.join(_out_dir(run), "trace"))
        began = time.perf_counter()
        reduced = reduce(load(path))
        took = time.perf_counter() - began
        if reduced is None:
            print(f"[host_spans] {path}: no {PREFIX}* span inside the "
                  "slice: this program writes none into a trace (the commit "
                  "before PR 38), or the cell's step is a bare jax.jit; the "
                  "metrics that read them are left out", flush=True)
            return None
        print(f"[host_spans] {path}: read in {took:.2f} s", flush=True)
        print("\n".join("[host_spans] " + s for s in span_table(reduced)),
              flush=True)
        bench = dict((run.get("trace") or {}).get("idle_gaps") or [])
        if reduced["idle_s"] and bench:
            named = reduced["idle_s"] - reduced["idle_no_span_s"]
            share = (named + bench.get("read_loss", 0.0)) / reduced["idle_s"]
            reduced["bench_idle_gaps"] = bench
            reduced["attributed_share"] = share
            print(f"[host_spans] {100 * share:.1f}% of the idle time is "
                  f"under a named {PREFIX} span or the benchmark's "
                  f"read_loss; the benchmark's own spans: {bench}",
                  flush=True)
        return reduced
    except Exception:       # the boundary: see the module's docstring
        print("[host_spans] could not read the program's spans:\n"
              + traceback.format_exc(), flush=True)
        return None


def _setup_part(run):
    try:
        setup_s = (run.get("e2e") or {}).get("setup_s")
        if setup_s is None:
            return None
        try:
            table = program_table()
        except (ImportError, AttributeError):
            print("[host_spans] this program keeps no set-up rows "
                  "(profiler.setup_stats: the commit before PR 38); "
                  "setup_trace_s, setup_lower_s and setup_import_s are "
                  "left out", flush=True)
            return None
        found = setup(table["rows"], process_born() + setup_s, setup_s)
        kept = {k: table.get(k) for k in ("kept", "seen", "short")}
        found["table"] = kept
        print("\n".join("[host_spans] " + s for s in setup_table(found)),
              flush=True)
        print(f"[host_spans] the program's table: {kept} (rows kept of "
              "those offered; jax's spans under a millisecond are counted "
              "and not kept)", flush=True)
        return found
    except Exception:       # the boundary
        print("[host_spans] could not read the set-up rows:\n"
              + traceback.format_exc(), flush=True)
        return None


def _report(run):
    key = (run["cell"].root, run["cell"].name)
    if key not in _seen:
        _seen[key] = {"spans": _spans_part(run), "setup": _setup_part(run)}
        try:
            with open(os.path.join(_out_dir(run), "host_spans.json"),
                      "w") as f:
                json.dump(_seen[key], f, indent=1, default=str)
        except OSError:
            print("[host_spans] could not write host_spans.json:\n"
                  + traceback.format_exc(), flush=True)
    return _seen[key]


def of(run):
    """`reduce` of the cell's last traced run, or None. Never raises."""
    return _report(run)["spans"]


def setup_of(run):
    """`setup` of this process's rows before its window, or None. Never
    raises."""
    return _report(run)["setup"]


def per_step_ms(run, pick):
    """Milliseconds a step of what `pick(reduced)` returns in seconds, over
    the `mx:train_step` calls of the slice; None without spans or steps."""
    reduced = of(run)
    if not reduced or not reduced["steps"]:
        return None
    seconds = pick(reduced)
    return None if seconds is None else 1e3 * seconds / reduced["steps"]


def span_seconds(reduced, name, field):
    """`field` of the dispatching thread's row for span `name`, or None."""
    for r in reduced["spans"]:
        if r["span"] == name and r["thread"] == "dispatch":
            return r[field]
    return None


def setup_phase_s(run, phase):
    """Seconds of one set-up phase before the window; None without the
    table, 0.0 for a phase with no row there."""
    found = setup_of(run)
    return None if found is None else found["phases"].get(phase, 0.0)
