"""Device milliseconds per member-step under the named scope
``sfno.decoder``: the pointwise decoder on the last block's output and
the state, 457 -> 384 -> 73 (``repro.core.sfno``)."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "sfno.decoder")
