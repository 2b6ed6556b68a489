"""Device milliseconds per member-step under the named scope ``sfno.sht``:
every forward and inverse SHT of the eight blocks (Legendre contraction
and longitude transform), the residual transforms of the first and last
blocks included (``repro.core.sfno``)."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "sfno.sht")
