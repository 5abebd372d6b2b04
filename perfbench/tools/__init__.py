"""What a builder runs by hand: repeat a cell and read its spread, sweep
an open-loop cell for its knee, look at a trace before writing a reader.
The parent processes here never import jax: a run holds the chip alone."""
