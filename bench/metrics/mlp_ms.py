"""Device milliseconds per member-step under the named scope ``fcn3.mlp``:
every block's GELU, two-layer MLP, layer scale and residual."""

from bench import scopes


def read(run: dict) -> float | None:
    return scopes.ms_per_member_step(run, "fcn3.mlp")
