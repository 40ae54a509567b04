"""Benchmark harness of the DPRT system: see BENCHMARK.json and PERF.md."""
