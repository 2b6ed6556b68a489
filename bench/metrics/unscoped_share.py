"""Share of the device's op time, up to the last completion, under none
of the program's named scopes (``bench.scopes.SCOPES``), as a
percentage."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.unscoped_share(run)
