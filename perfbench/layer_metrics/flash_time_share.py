"""Kernels: share of device busy time inside custom calls, which in a
TransformerLM step are the flash-attention Pallas kernels and nothing else
(forward, recomputed forward, dq, dk/dv; 96 in GPT-2 medium's program).
trace_reduce labels an operation "<opcode> <name> <type>". A roofline share
needs a stable kernel name inside the program: the `tracing` issue's."""

META = {"layer": "kernels", "moves": "train_items_per_s", "unit": "%",
        "better": "lower", "source": "device_trace"}



def read(run):
    ops = (run["trace"] or {}).get("op_seconds")
    if not ops:
        return None
    busy = sum(ops.values())
    hit = sum(v for k, v in ops.items() if k.startswith("custom-call "))
    return 100.0 * hit / busy if busy else None
