"""Whose device time it is: the program's names on the operations of a trace.

`trace_reduce.py` reads WHEN the device worked, through
`jax.profiler.ProfileData`, which shows three stats per "XLA Ops" event and
nothing else. The same `.xplane.pb` holds, on each operation's
`XEventMetadata`, what XLA knew about the instruction: `tf_op` (jax's
`op_name`: the path of `jax.named_scope`s and transforms the program was
traced under), `hlo_category`, `flops`, `bytes_accessed`, `source`. `walk()`
reads them off the protobuf wire with nothing imported; `reduce()` joins them
to the self time of the operations inside the traced slice; the functions at
the end are what the per-layer metric files in `layer_metrics/` call.

The contract with the program is three top words, `forward`, `loss` and
`optimizer` (`parallel/train.py`, `models/transformer.py`), and the names of
the Pallas kernels (`flash_fwd`, `flash_bwd_dq`, `flash_bwd_dkv`). Backward
and recomputation are written by jax itself:

    jit(step)/jvp(forward)/layer1/mlp/tanh                          forward
    jit(step)/transpose(jvp(forward))/layer0/../checkpoint/attn/mul backward
    jit(step)/transpose(jvp(forward))/../rematted_computation/mlp/dot_general
                                                          recomputed forward
    jit(step)/optimizer/mul
    a;b             one fused instruction may list several: the first counts

A fusion carries the metadata of the instruction XLA chose for it (its root,
as a rule): a convolution fused with a BatchNorm epilogue is ONE operation
under ONE scope. Every share read from here is a share of operations by the
scope of their root.

A program without the names (the parent commit of the PR that added them, or
an executable from a cache filled before it) has no top word on any
operation: every reader then returns None, and the harness leaves the metric
out.
"""
from __future__ import annotations

import json
import os
import re
import struct
import time
import traceback

from .spans import ANCHOR
from .trace_reduce import (COLLECTIVE, DEVICE_PLANE, OP_LINE, find_xplane,
                           op_label, self_seconds)

TOP_WORDS = ("forward", "loss", "optimizer")
BN_WORDS = ("BatchNorm", "FusedBNAddReLU")
FLASH_BWD = ("flash_bwd_dq", "flash_bwd_dkv")
NOT_WORDS = ("checkpoint", "rematted_computation")


# -- the wire -----------------------------------------------------------------
# tsl/profiler/protobuf/xplane.proto. XSpace.planes = 1. XPlane: name = 2,
# lines = 3, event_metadata = 4 and stat_metadata = 5 (maps: key = 1,
# value = 2), stats = 6. XLine: name = 2, timestamp_ns = 3, events = 4.
# XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3. XEventMetadata:
# id = 1, name = 2, stats = 5. XStatMetadata: id = 1, name = 2. XStat:
# metadata_id = 1, double = 2, uint64 = 3, int64 = 4, str = 5, bytes = 6,
# ref = 7 (an id of stat_metadata, standing for its name).

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start=0, end=None):
    """(field number, wire type, value) of one message; a length-delimited
    or fixed-width value is its (start, end) inside `buf`."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} at byte {i}: not an "
                                 "xplane")
            value = (i, i + size)
            i += size
        yield key >> 3, kind, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _number(text):
    """Numbers such as `flops` arrive as strings in these traces."""
    try:
        return int(text)
    except ValueError:
        try:
            return float(text)
        except ValueError:
            return text


def _breakdown(raw):
    """XLA's `memory_access_breakdown` (a serialized message of repeated
    {operation_type = 1: 1 read, 2 write; memory_space = 2: 1 HBM, 3 the
    on-chip memory a layout marks `S(1)`; bytes_accessed = 3}) as
    [(operation, space, bytes)]."""
    out = []
    for num, _, v in _fields(raw):
        if num == 1:
            entry = {n2: v2 for n2, _, v2 in _fields(raw, *v)}
            out.append((entry.get(1, 0), entry.get(2, 0), entry.get(3, 0)))
    return out


def _stat(buf, span, stat_names):
    name = value = None
    for num, kind, v in _fields(buf, *span):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", buf[v[0]:v[1]])[0]
        elif num == 3:
            value = v
        elif num == 4:                    # int64: two's complement
            value = v - (1 << 64) if v >> 63 else v
        elif num == 5:
            value = _number(_text(buf, v))
        elif num == 6:
            value = bytes(buf[v[0]:v[1]])
            if name == "memory_access_breakdown":
                value = _breakdown(value)
        elif num == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_value(buf, span):
    for num, _, v in _fields(buf, *span):
        if num == 2:
            return v
    return None


def _plane(buf, span):
    lines, meta_spans, stat_names, name = [], [], {}, ""
    for num, _, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            meta_spans.append(_map_value(buf, v))
        elif num == 5:
            sid, sname = 0, ""
            for n2, _, v2 in _fields(buf, *_map_value(buf, v)):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = _text(buf, v2)
            stat_names[sid] = sname
    metadata = {}
    for ms in meta_spans:               # after every stat name is known
        mid, mname, stats = 0, "", {}
        for num, _, v in _fields(buf, *ms):
            if num == 1:
                mid = v
            elif num == 2:
                mname = _text(buf, v)
            elif num == 5:
                k, val = _stat(buf, v, stat_names)
                stats[k] = val
        metadata[mid] = {"name": mname, "stats": stats}
    out_lines = []
    for ls in lines:
        lname, t0, events = "", 0, []
        for num, _, v in _fields(buf, *ls):
            if num == 2:
                lname = _text(buf, v)
            elif num == 3:
                t0 = v
            elif num == 4:
                mid = off = dur = 0
                for n2, _, v2 in _fields(buf, *v):
                    if n2 == 1:
                        mid = v2
                    elif n2 == 2:
                        off = v2
                    elif n2 == 3:
                        dur = v2
                events.append((mid, off, dur))
        out_lines.append({"name": lname, "timestamp_ns": t0,
                          "events": events})
    return {"name": name, "lines": out_lines, "event_metadata": metadata}


def walk(path):
    """The planes of one `.xplane.pb`: [{"name", "lines": [{"name",
    "timestamp_ns", "events": [(metadata_id, offset_ps, duration_ps)]}],
    "event_metadata": {id: {"name", "stats": {name: value}}}}]."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(buf, v) for num, _, v in _fields(buf) if num == 1]


# -- a path of names ------------------------------------------------------------

WRAPPER = re.compile(r"^([\w.\-]+)\((.*)\)$")
SHAPE = re.compile(r"\b(pred|[subf]\d+|bf16|c64|c128)\[([\d,]*)\]")
ITEMSIZE = {"pred": 1, "bf16": 2, "c64": 8, "c128": 16}


def parse_path(tf_op):
    """{"words", "backward", "recomputed", "primitive"} of one `tf_op`
    (the grammar is in the module's docstring). `words` are the scopes in
    order, wrappers taken off, without jax's own `checkpoint` /
    `rematted_computation` and without repeats."""
    first = (tf_op or "").split(";")[0].strip().rstrip(":")
    parts = first.split("/") if first else []
    words, wrappers = [], set()
    for part in parts[:-1]:
        outer = None
        while (m := WRAPPER.match(part)):
            outer = outer or m.group(1)
            wrappers.add(m.group(1))
            part = m.group(2)
        if outer in ("jit", "pjit"):        # a function's name, not a scope
            continue
        if part and part not in NOT_WORDS and part not in words:
            words.append(part)
    return {"words": words, "backward": "transpose" in wrappers,
            "recomputed": "rematted_computation" in parts,
            "primitive": parts[-1] if parts else ""}


def shapes_of(text):
    """(results, operands) of an HLO instruction's text, each a list of
    "dtype[dims]" without layouts: a custom call's only record of size."""
    _, _, rest = text.partition(" = ")
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):       # the type may be a tuple "(.., ..)"
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            end = i
            break
    args = rest[end:].split("), ", 1)[0]            # attributes left out
    found = [[f"{m.group(1)}[{m.group(2)}]" for m in SHAPE.finditer(part)]
             for part in (rest[:end], args)]
    return found[0], found[1]


def shape_dims(shape):
    return [int(d) for d in shape.rstrip("]").split("[")[1].split(",") if d]


def shape_bytes(shape):
    dtype = shape.split("[")[0]
    size = ITEMSIZE.get(dtype) or int(re.sub(r"\D", "", dtype)) // 8
    for d in shape_dims(shape):
        size *= d
    return size


# -- the reduction ------------------------------------------------------------

def reduce(path):
    """{"window_s", "chips", "busy_s", "rows"} of the traced slice (the
    anchor span, as `trace_reduce.load` finds it). One row per distinct
    instruction and scope: self time inside the slice and calls, both
    averaged over chips; `flops`, `bytes` and `hbm_bytes` are XLA's own, for
    ONE call."""
    planes = walk(path)
    anchor = None
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        ids = {k for k, m in plane["event_metadata"].items()
               if m["name"] == ANCHOR}
        for line in plane["lines"]:
            for mid, off, dur in line["events"]:
                if mid in ids:
                    start = line["timestamp_ns"] * 1e-9 + off * 1e-12
                    anchor = (start, start + dur * 1e-12)
    if anchor is None:
        raise ValueError(f"the trace holds no anchor span {ANCHOR!r}")
    t0, t1 = anchor
    rows, chips = {}, 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] != OP_LINE:
                continue
            chips += 1
            base = line["timestamp_ns"] * 1e-9
            inside = [(mid, max(s, t0), min(e, t1)) for mid, s, e in (
                (mid, base + off * 1e-12, base + (off + dur) * 1e-12)
                for mid, off, dur in line["events"])
                if min(e, t1) > max(s, t0)]
            calls = {}
            for mid, _, _ in inside:
                calls[mid] = calls.get(mid, 0) + 1
            for mid, sec in self_seconds(inside).items():
                meta = plane["event_metadata"][mid]
                stats = meta["stats"]
                tf_op = str(stats.get("tf_op", ""))
                row = rows.get((meta["name"], tf_op))
                if row is None:
                    results, operands = shapes_of(meta["name"])
                    row = rows[(meta["name"], tf_op)] = dict(
                        parse_path(tf_op), op=op_label(meta["name"]),
                        tf_op=tf_op.split(";")[0].rstrip(":"),
                        category=str(stats.get("hlo_category", "")),
                        seconds=0.0, calls=0,
                        flops=stats.get("flops", 0),
                        bytes=stats.get("bytes_accessed", 0),
                        hbm_bytes=_hbm_bytes(stats),
                        results=results, operands=operands)
                row["seconds"] += sec
                row["calls"] += calls[mid]
    if not chips:
        raise ValueError("the trace holds no device operations")
    out = sorted(rows.values(), key=lambda r: -r["seconds"])
    for row in out:
        row["seconds"] /= chips
        row["calls"] /= chips
    return {"window_s": t1 - t0, "chips": chips,
            "busy_s": sum(r["seconds"] for r in out), "rows": out}


def _hbm_bytes(stats):
    """The part of `bytes_accessed` that goes to HBM. XLA keeps some
    operands in on-chip memory (`S(1)` in their layout), and counts their
    bytes too: read against the HBM peak, a ResNet-50 fusion with one of
    three operands on the chip came to 122%."""
    parts = stats.get("memory_access_breakdown")
    if not parts:
        return stats.get("bytes_accessed", 0)
    return sum(size for _, space, size in parts if space == 1)


def top_word(row):
    return next((w for w in row["words"] if w in TOP_WORDS), None)


def table(reduced):
    """Lines of the table by top word and by (top word, second word):
    share of busy time, forward / backward / recomputed apart."""
    busy = reduced["busy_s"] or 1.0
    groups = {}
    for row in reduced["rows"]:
        top = top_word(row)
        if top is None:
            key = ("(unnamed)", row["category"] or "(no category)")
        else:
            rest = [w for w in row["words"][row["words"].index(top) + 1:]
                    if not re.fullmatch(r"layer\d+", w)]
            key = (top, rest[0] if rest else "(itself)")
        phase = ("recomputed" if row["recomputed"] else
                 "backward" if row["backward"] else "forward")
        for k in (key[:1], key):
            g = groups.setdefault(k, {"forward": 0.0, "backward": 0.0,
                                      "recomputed": 0.0})
            g[phase] += row["seconds"]
    lines = [f"{'scope':<34}{'% busy':>8}{'fwd':>8}{'bwd':>8}{'remat':>8}"]
    tops = sorted({k[0] for k in groups},
                  key=lambda t: -sum(groups[(t,)].values()))
    for top in tops:
        keys = [(top,)] + sorted(
            (k for k in groups if len(k) == 2 and k[0] == top),
            key=lambda k: -sum(groups[k].values()))
        for k in keys:
            g = groups[k]
            name = k[0] if len(k) == 1 else "  " + k[1]
            lines.append(f"{name:<34}{100 * sum(g.values()) / busy:8.2f}"
                         f"{100 * g['forward'] / busy:8.2f}"
                         f"{100 * g['backward'] / busy:8.2f}"
                         f"{100 * g['recomputed'] / busy:8.2f}")
    return lines


# -- what a flash call requires -------------------------------------------------

def flash_flops(bh, t, d, backward=False):
    """Required FLOPs of causal attention over (BH, T, D). Forward: QK^T
    and PV, each 2 T^2 D a head, halved by the mask. Backward, dq and dk/dv
    kernels TOGETHER: dV, dP, dQ, dK; the scores both recompute are not
    required work."""
    return (4 if backward else 2) * bh * t * t * d


def flash_least_seconds(row, peaks, backward=False):
    """(least seconds, "FLOPs" | "bytes") of one call of `flash_fwd`, or
    of one `flash_bwd_dq` call AND the `flash_bwd_dkv` call beside it. Bytes:
    every operand and result once; the pair's are dq's own plus dk and dv,
    which have the shapes of dq's operands k and v."""
    bh, t, d = shape_dims(row["operands"][0])
    moved = sum(map(shape_bytes, row["operands"] + row["results"]))
    if backward:
        moved += sum(map(shape_bytes, row["operands"][1:3]))
    by_flops = flash_flops(bh, t, d, backward) / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "FLOPs" if by_flops >= by_bytes \
        else "bytes"


# -- what the per-layer metric files call ------------------------------------------

_seen = {}          # trace file -> (mtime, reduced or None): seven readers,
#                     one walk; the record a reader is handed names no file


def of(run):
    """The reduction of the cell's last traced run (`perfbench/out/<cell>/
    trace`), or None where the program carries no names (no operation under
    `forward`, `loss` or `optimizer`: the parent commit, a stale cache) or
    there is nothing to read. Never raises: a reader that did would fail a
    traced run of a program that simply lacks the names. The first call
    writes `scopes.json` beside the trace and logs the table."""
    out_dir = os.path.join(run["cell"].root, "perfbench", "out",
                           run["cell"].name)
    try:
        path = find_xplane(os.path.join(out_dir, "trace"))
        mtime = os.path.getmtime(path)
        if _seen.get(path, (None,))[0] == mtime:
            return _seen[path][1]
        began = time.perf_counter()
        reduced = reduce(path)
        with open(os.path.join(out_dir, "scopes.json"), "w") as f:
            json.dump(reduced, f, indent=0)
        named = any(top_word(r) for r in reduced["rows"])
        print(f"[op_scopes] {path}: {len(reduced['rows'])} operations, busy "
              f"{reduced['busy_s']:.6f} s on {reduced['chips']} chip(s), read "
              f"in {time.perf_counter() - began:.2f} s", flush=True)
        if named:
            print("\n".join("[op_scopes] " + s for s in table(reduced)),
                  flush=True)
        else:
            print(f"[op_scopes] no operation carries any of {TOP_WORDS} in "
                  "its path: this program has no names (or its executable "
                  "came from a cache filled before it had); the metrics "
                  "that read them are left out", flush=True)
        _seen[path] = (mtime, reduced if named else None)
        return _seen[path][1]
    except Exception:       # the boundary: see the docstring
        print("[op_scopes] could not read the trace's names:\n"
              + traceback.format_exc(), flush=True)
        return None


def share(run, pick):
    """Percent of busy time in the rows `pick` accepts, or None."""
    reduced = of(run)
    if not reduced or not reduced["busy_s"]:
        return None
    return 100.0 * sum(r["seconds"] for r in reduced["rows"]
                       if pick(r)) / reduced["busy_s"]


def has_word(*words):
    return lambda row: any(w in row["words"] for w in words)


def flash_roofline(run, backward=False):
    """Percent: least time over mean device time, of `flash_fwd` calls
    (first and recomputed runs alike) or of a layer's backward pair."""
    reduced = of(run)
    if not reduced:
        return None
    lead = "flash_bwd_dq" if backward else "flash_fwd"
    timed = FLASH_BWD if backward else ("flash_fwd",)
    rows = [r for r in reduced["rows"] if r["category"] == "custom-call"]
    leads = [r for r in rows if lead in r["words"] and r["operands"]]
    seconds = sum(r["seconds"] for r in rows if has_word(*timed)(r))
    if not leads or not seconds:
        return None
    least = 0.0
    for r in leads:
        one, bound = flash_least_seconds(r, run["peaks"], backward)
        least += one * r["calls"]
    calls = sum(r["calls"] for r in leads)
    print(f"[op_scopes] {'+'.join(timed)}: least {1e6 * least / calls:.1f} "
          f"us a call (bound by {bound}), measured "
          f"{1e6 * seconds / calls:.1f} us over {calls:g} calls of "
          f"{leads[0]['operands'][0]}", flush=True)
    return 100.0 * least / seconds


def bn_hbm_roofline(run):
    """Percent of the HBM peak: the bytes XLA says the BatchNorm operations
    move to and from HBM (collectives left out) over their seconds."""
    reduced = of(run)
    if not reduced:
        return None
    rows = [r for r in reduced["rows"]
            if has_word(*BN_WORDS)(r) and not COLLECTIVE.match(r["op"])]
    seconds = sum(r["seconds"] for r in rows)
    if not seconds:
        return None
    moved = sum(r["hbm_bytes"] * r["calls"] for r in rows)
    return 100.0 * moved / seconds / run["peaks"]["hbm_bytes_per_s"]
