"""Plain float32 FCN3 reference for the benchmark's correctness check.

A frozen copy of the geometry and reference contractions the forecast
service started from, trimmed to the FFT/einsum paths.  It imports
nothing from the service's package and builds its own tables, weights
and data, so no change to the service can move what it computes.
"""
