"""Device milliseconds per member-step under the named scope
``fcn3.local_conv``: the local blocks' concat with the conditioning and
DISCO conv."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "fcn3.local_conv")
