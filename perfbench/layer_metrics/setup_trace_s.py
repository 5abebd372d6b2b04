"""Executable cache: seconds jax spent TRACING functions to jaxprs before the
window (the union of the program's set-up rows of phase `trace`). A warm
start pays them whole: jax's persistent cache and the program's executable
cache are both keyed by what tracing produces. None from a program that
keeps no set-up rows (perfbench/host_spans.py)."""
from perfbench import host_spans

META = {"layer": "executable_cache", "moves": "setup_s", "unit": "s",
        "better": "lower", "source": "program_counter"}


def read(run):
    return host_spans.setup_phase_s(run, "trace")
