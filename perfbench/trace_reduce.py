"""From the profiler's trace to numbers: the only reader of a device
trace in the repository, kept here so no PR that claims a gain can move it.

`load()` turns an `.xplane.pb` (jax.profiler.ProfileData, nothing else)
into plain lists; everything after that is arithmetic on intervals and is
tested on a recorded trace under tests/perfbench/data/.

Times are seconds on the trace's clock. The benchmark's own spans are on
its `time.perf_counter` clock; the anchor span (spans.ANCHOR) is written on
both, and its two starts give the offset between them.
"""
from __future__ import annotations

import glob
import os
import re

from .spans import ANCHOR

# What a TPU v5e trace of jax 0.9.0 holds (looked at by hand, PR 22): one
# plane per chip, "/device:TPU:<n>", with the lines "Steps", "XLA Modules"
# (one event per program run), "XLA Ops" (one event per HLO instruction as
# the core ran it, back to back, named by the instruction's whole text) and
# "Async XLA Ops" (copies and collectives in flight BESIDE the core's work:
# not busy time). Host threads are lines of "/host:CPU".
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
NO_SPAN = "(no benchmark span)"


def op_label(text):
    """"<opcode> <instruction> <result type>" from an HLO instruction's
    text, e.g. "%fusion.7 = f32[32,1024]{1,0} fusion(bf16[...] %p), kind=.."
    -> "fusion fusion.7 f32[32,1024]". The opcode leads so that a reader
    can tell a custom call (a Pallas kernel) or a collective by prefix;
    the instruction's name keeps labels of different operations apart."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:100]
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):       # the type may be a tuple "(.., ..)"
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            end = i
            break
    opcode = rest[end + 1:].split("(", 1)[0]
    kind = re.sub(r"\{[^}]*\}", "", rest[:end])       # layouts out
    return f"{opcode} {name.lstrip('%')} {kind}"[:100]


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    """{"ops": {chip: [(name, start_s, end_s)]}, "anchor": (start_s, end_s)
    or None, "lines": {plane: {line: n_events}}} from one .xplane.pb."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, anchor, lines = {}, None, {}
    for plane in data.planes:
        seen = lines.setdefault(plane.name, {})
        chip = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = list(line.events)
            seen[line.name] = len(events)
            if chip and line.name == OP_LINE:
                ops[int(chip.group(1))] = [
                    (op_label(e.name), e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9) for e in events]
            elif not chip:
                for e in events:
                    if e.name == ANCHOR:
                        anchor = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
    return {"ops": ops, "anchor": anchor, "lines": lines}


# -- intervals: lists of (start, end), seconds ------------------------------

def union(intervals):
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of union `a` that union `b` does not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a, b):
    """Seconds that unions `a` and `b` share."""
    return total(a) - total(subtract(a, b))


def self_seconds(events):
    """{name: seconds}: each event's duration less what the events nested
    inside it cover (a `while` op spans its body's ops on the same line)."""
    out, stack = {}, []        # stack of [name, end, seconds of children]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, start, child = stack.pop()
            out[name] = out.get(name, 0.0) + (end - start) - child
            if stack:
                stack[-1][3] += end - start
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            e = min(e, stack[-1][1])        # a child never outlasts its parent
        if e > s:
            stack.append([name, e, s, 0.0])
    close(float("inf"))
    return out


# -- the reduction ------------------------------------------------------------

def reduce_trace(trace, spans=(), anchor_bench=None, top=10):
    """Summary of the traced slice. `spans` are the benchmark's
    (name, t0, t1) rows on its own clock and `anchor_bench` its record of
    the anchor span; without them idle gaps go unattributed."""
    if trace["anchor"] is None:
        raise ValueError("the trace holds no anchor span "
                         f"{ANCHOR!r}: lines {trace['lines']}")
    if not trace["ops"]:
        raise ValueError("the trace holds no device operations: lines "
                         f"{trace['lines']}")
    t0, t1 = trace["anchor"]
    window = t1 - t0
    per_chip, by_name = {}, {}
    for chip, events in sorted(trace["ops"].items()):
        inside = [(n, max(s, t0), min(e, t1)) for n, s, e in events
                  if min(e, t1) > max(s, t0)]
        busy = union((s, e) for _, s, e in inside)
        coll = union((s, e) for n, s, e in inside if COLLECTIVE.match(n))
        rest = union((s, e) for n, s, e in inside
                     if not COLLECTIVE.match(n))
        per_chip[chip] = {
            "busy_s": total(busy), "gaps": subtract([(t0, t1)], busy),
            "collective_s": total(coll),
            "collective_exposed_s": total(subtract(coll, rest))}
        for name, sec in self_seconds(inside).items():
            by_name[name] = by_name.get(name, 0.0) + sec
    n = len(per_chip)
    busy_all = sum(c["busy_s"] for c in per_chip.values())
    worst = max(per_chip, key=lambda c: total(per_chip[c]["gaps"]))
    exposed = max(c["collective_exposed_s"] for c in per_chip.values())
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    out = {
        "window_s": window, "chips": n, "busy_s": busy_all / n,
        "idle_share_worst": total(per_chip[worst]["gaps"]) / window,
        "collective_s_worst": max(c["collective_s"]
                                  for c in per_chip.values()),
        "collective_exposed_share_worst": exposed / window,
        "device_ops": [[k, v / n] for k, v in ranked[:top]],
        "top_op_share": ranked[0][1] / busy_all if busy_all else None,
        "op_seconds": {k: v / n for k, v in ranked},
    }
    if anchor_bench is not None:
        shift = t0 - anchor_bench[0]
        named = {}
        for name, a, b in spans:
            named.setdefault(name, []).append((a + shift, b + shift))
        gaps = per_chip[worst]["gaps"]
        rows = {name: overlap(gaps, union(iv)) for name, iv in named.items()}
        covered = union(iv for ivs in named.values() for iv in ivs)
        rows[NO_SPAN] = total(subtract(gaps, covered))
        out["idle_gaps"] = [[k, v] for k, v in sorted(
            rows.items(), key=lambda kv: -kv[1]) if v > 0][:top]
    return out
