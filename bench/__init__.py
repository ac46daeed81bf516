"""Benchmark harness: see bench/common.py and PERF.md."""
