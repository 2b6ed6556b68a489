"""Median device time of one execution of the engine's chunk program
(``jit_chunk`` on the device's "XLA Modules" line) inside the window:
the device side of ``chunk_interval_p50_s``."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.chunk_device_s(run)
