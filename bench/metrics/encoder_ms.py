"""Device milliseconds per member-step under the named scope
``fcn3.encoder``: the three DISCO encoders and their concat
(``FCN3._encode``)."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "fcn3.encoder")
