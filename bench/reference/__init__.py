"""Plain float32 references for the benchmark's correctness check: one
module per model family (``bench/family.py``), and what they share.

A frozen copy of the geometry and reference contractions the forecast
service started from, trimmed to the FFT/einsum paths, and the synthetic
data (``data.py``).  It imports nothing from the service's package and
builds its own tables, weights and data, so no change to the service can
move what it computes.
"""
