"""Frontend: seconds from the top to the bottom of
`incubator_mxnet_tpu/__init__.py` (the program's set-up row of phase
`import`): the registry, ndarray and what they import, jax's own import
where the package is imported first. None from a program that keeps no
set-up rows (perfbench/host_spans.py)."""
from perfbench import host_spans

META = {"layer": "frontend", "moves": "setup_s", "unit": "s",
        "better": "lower", "source": "program_counter"}


def read(run):
    return host_spans.setup_phase_s(run, "import")
