"""Benchmark of the served scan path; see bench/run.py."""
