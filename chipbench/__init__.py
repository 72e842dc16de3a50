"""On-chip benchmark of the JAX training and serving paths (see
BENCHMARK.json at the repository root and PERF.md)."""
