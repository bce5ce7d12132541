"""The benchmark: a data-driven harness that runs one cell on the chip."""
