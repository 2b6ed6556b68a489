"""Chip benchmark of the served forecast (see PERF.md)."""
