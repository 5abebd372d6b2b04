"""Kernels: the least time one `flash_win_fwd` call (causal attention inside
a window: query i reads keys j with 0 <= i - j < W) could take on this chip,
over the mean device time of its calls in the slice.

Required work from the call's operands q, k (N, T, Cqk) and v (N, T, Cv),
rows of heads side by side, and the configuration's `sliding_window` W: the
kept (i, j) pairs are sum_i min(i + 1, W) = W T - W (W - 1) / 2 for T >= W;
forward QK^T and PV cost 2 (Cqk + Cv) a pair and a row of the batch;
backward, `flash_win_bwd_dq` and `flash_win_bwd_dkv` TOGETHER, twice that
(dQ and dK over Cqk, dV and dP over Cv; the scores both recompute are not
required work). Bytes: every operand and result once. Least time =
max(FLOPs / bfloat16 peak, bytes / HBM peak). `flash_win_bwd_roofline.py`
reads the backward pair with these functions."""
from perfbench import op_scopes

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "higher", "source": "device_trace"}

BWD = ("flash_win_bwd_dq", "flash_win_bwd_dkv")


def kept_pairs(t, window):
    """(i, j) with 0 <= i - j < window among t positions."""
    w = min(window, t)
    return w * t - w * (w - 1) // 2


def window_flops(n, t, cqk, cv, window, backward=False):
    return (2 if backward else 1) * 2 * (cqk + cv) * n * kept_pairs(t, window)


def least_seconds(row, window, peaks, backward=False):
    """(least seconds, "FLOPs" | "bytes") of one forward call, or of one
    dq call AND the dk/dv call beside it (whose results have the shapes of
    dq's operands k and v)."""
    n, t, cqk, cv = op_scopes.flash_dims(row)
    moved = sum(map(op_scopes.shape_bytes, row["operands"] + row["results"]))
    if backward:
        moved += sum(map(op_scopes.shape_bytes, row["operands"][1:3]))
    by_flops = window_flops(n, t, cqk, cv, window, backward) \
        / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "FLOPs" if by_flops >= by_bytes \
        else "bytes"


def roofline(run, backward=False):
    reduced = op_scopes.of(run)
    window = run["cell"].config.get("sliding_window")
    if not reduced or not window:
        return None
    lead = BWD[0] if backward else "flash_win_fwd"
    timed = BWD if backward else ("flash_win_fwd",)
    rows = [r for r in reduced["rows"] if r["category"] == "custom-call"]
    leads = [r for r in rows if lead in r["words"] and r["operands"]]
    seconds = sum(r["seconds"] for r in rows if op_scopes.has_word(*timed)(r))
    if not leads or not seconds:
        return None
    least = 0.0
    for r in leads:
        one, bound = least_seconds(r, window, run["peaks"], backward)
        least += one * r["calls"]
    calls = sum(r["calls"] for r in leads)
    print(f"[flash_win_roofline] {'+'.join(timed)}: least "
          f"{1e6 * least / calls:.1f} us a call (window {window}, bound by "
          f"{bound}), measured {1e6 * seconds / calls:.1f} us over {calls:g} "
          f"calls of {leads[0]['operands'][0]}", flush=True)
    return 100.0 * least / seconds


def read(run):
    return roofline(run)
