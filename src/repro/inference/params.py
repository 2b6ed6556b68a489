"""Forecast-model parameter loading.

One loader shared by every entry point that needs ready-to-serve params
(the one-shot serve CLI, the serving pool's model bundles), so all of
them stay bit-identical by construction.
"""

from __future__ import annotations

import jax


def load_params(model, ds, buffers, state0, ckpt: str | None = None):
    """Checkpoint restore, or the model family's own params for a
    service started without one (``model.default_params``: FCN3's
    calibrated init, SFNO's plain seeded init)."""
    if ckpt:
        from repro.train import checkpoint as ckptlib
        template = {"params": jax.eval_shape(model.init,
                                             jax.random.PRNGKey(0))}
        restored, _ = ckptlib.restore_checkpoint(ckpt, template)
        return restored["params"]
    return model.default_params(ds, buffers, state0)
