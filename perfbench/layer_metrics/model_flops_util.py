"""Device: the FLOP/s the forward and backward passes REQUIRE at the
measured throughput, over the chips' bfloat16 peak. Recomputation does not
count; attention under a causal mask counts once (perfbench/flops.py)."""

META = {"layer": "device", "moves": "train_items_per_s", "unit": "%",
        "better": "higher", "source": "host_clock"}


def read(run):
    d = run["driver"]
    if "items_per_s" not in d or "flops_per_item" not in d:
        return None
    peak = run["cell"].chips * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * d["items_per_s"] * d["flops_per_item"] / peak
