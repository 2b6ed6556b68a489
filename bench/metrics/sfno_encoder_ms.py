"""Device milliseconds per member-step under the named scope
``sfno.encoder``: the pointwise encoder, 73 -> 384 -> 384 on the
721x1440 grid (``repro.core.sfno``)."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "sfno.encoder")
