"""Legendre contraction kernel (``kernels/legendre``) of the SFNO blocks'
SHTs, 3 on the 721x1440 grid and 15 on the latent grid a member-step:
the algorithm's least time (``bench/counts/sfno.py``) over its device
time."""

from bench.metrics._kernel import roofline


def read(run: dict) -> float | None:
    return roofline(run, "legendre_contract", "legendre")
