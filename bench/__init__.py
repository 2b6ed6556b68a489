"""Chip benchmark of the served FCN3 forecast (see PERF.md)."""
