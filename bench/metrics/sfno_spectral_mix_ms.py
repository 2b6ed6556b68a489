"""Device milliseconds per member-step under the named scope
``sfno.spectral_mix``: the eight blocks' complex per-degree channel
mixes (``repro.core.sfno``)."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "sfno.spectral_mix")
