"""Device milliseconds per member-step under the named scope
``engine.products``: the scan body's in-scan products (scores, spectra,
diagnostics)."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "engine.products")
