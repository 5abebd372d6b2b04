"""The repository's benchmark: one command per cell, every cell as data.

`python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` measures one entry of `BENCHMARK.json`'s `workloads` on
the chip it is started on. README.md says how a later PR adds a cell.
Nothing in this package imports jax or the program at import time.
"""
