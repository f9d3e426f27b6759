"""The chip benchmark: one command runs one cell of BENCHMARK.json once."""
