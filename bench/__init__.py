"""Benchmark of the serving path: see BENCHMARK.json and PERF.md."""
