"""Executable cache: seconds jax spent turning traced jaxprs into MLIR
modules before the window (the union of the program's set-up rows of phase
`lower`): paid on a warm start too, since the module is what the persistent
cache's key is computed from. None from a program that keeps no set-up rows
(perfbench/host_spans.py)."""
from perfbench import host_spans

META = {"layer": "executable_cache", "moves": "setup_s", "unit": "s",
        "better": "lower", "source": "program_counter"}


def read(run):
    return host_spans.setup_phase_s(run, "lower")
