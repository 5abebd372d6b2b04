"""One module per kind of run: `run(r)` plays the cell's traffic against
the job its family builds and returns what it measured."""
