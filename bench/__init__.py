"""Chip benchmark of the exact-quantile engine and service (see BENCHMARK.json)."""
