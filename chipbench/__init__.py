"""The on-chip benchmark: cells of BENCHMARK.json, measured by one command."""
