"""Device milliseconds per member-step under the named scope
``engine.noise``: the scan body's noise field (inverse SHT), centering
and AR(1) transition."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "engine.noise")
