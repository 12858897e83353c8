"""Chip benchmark of the sparse-prefill serving engine (see PERF.md)."""
