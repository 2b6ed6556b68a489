"""Device milliseconds per member-step under the named scope ``sfno.mlp``:
each block's instance norms, inner skip and two-layer MLP
(``repro.core.sfno``)."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "sfno.mlp")
