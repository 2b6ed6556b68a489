"""Banded DISCO kernel (``kernels/disco``): the algorithm's least time
for the rings it computes, over its device time."""

from bench.metrics._kernel import roofline


def read(run: dict) -> float | None:
    return roofline(run, "disco_band_contract", "disco_kernel")
