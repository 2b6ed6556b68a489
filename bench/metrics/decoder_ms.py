"""Device milliseconds per member-step under the named scope
``fcn3.decoder``: upsample, the DISCO decoder calls, concat and the
output softclamp."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "fcn3.decoder")
