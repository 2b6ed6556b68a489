"""Device milliseconds per member-step under the named scope
``fcn3.spectral_conv``: the global blocks' concat with the conditioning,
forward SHT, spectral mix and inverse SHT."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "fcn3.spectral_conv")
