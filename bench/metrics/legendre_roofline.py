"""Legendre contraction kernel (``kernels/legendre``) of the global
blocks' SHTs: the algorithm's least time over its device time."""

from bench.metrics._kernel import roofline


def read(run: dict) -> float | None:
    return roofline(run, "legendre_contract", "legendre")
