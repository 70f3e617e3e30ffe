"""The chip benchmark: one cell of BENCHMARK.json per run, on a TPU."""
