"""Distributed, ensemble-parallel CRPS (paper G.2.4, Algorithm 3).

Ensemble members are computationally independent through the whole forward
pass; the only cross-member communication of a training step is here.  The
paper transposes data globally so the ensemble dimension becomes rank-local
while the (flattened) spatial dimension is scattered further -- exactly one
``all_to_all`` over the ensemble axis -- then evaluates the rank-local CRPS
kernel and averages with quadrature weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


from repro.core import crps as crpslib


def dist_crps(ens_local: jax.Array, obs_local: jax.Array,
              weights_local: jax.Array, ens_axis: str,
              fair: bool = False) -> jax.Array:
    """Rank-local body of the distributed nodal CRPS.

    ens_local: (Eloc, ..., S) this rank's ensemble members over the local
      flattened spatial block S (S divisible by the ensemble axis size).
    obs_local: (..., S) ground truth on the same block.
    weights_local: (S,) quadrature weights of the block, globally
      normalized (sum over *all* ranks and points == 1).
    Returns the scalar spatially averaged CRPS (identical on all ranks).
    """
    n_e = jax.lax.axis_size(ens_axis)
    # 1) gather ensemble, scatter space: (Eloc,...,S) -> (E, ..., S/nE)
    ens = jax.lax.all_to_all(ens_local, ens_axis, split_axis=ens_local.ndim - 1,
                             concat_axis=0, tiled=True)
    s_sub = ens.shape[-1]
    # matching spatial sub-block of the observation / weights: this rank's
    # ensemble index selects the slice
    idx = jax.lax.axis_index(ens_axis)
    obs = jax.lax.dynamic_slice_in_dim(obs_local, idx * s_sub, s_sub, -1)
    w = jax.lax.dynamic_slice_in_dim(weights_local, idx * s_sub, s_sub, -1)
    # 2) rank-local CRPS kernel over the full ensemble
    pt = crpslib.crps_ensemble(ens, obs, axis=0, fair=fair)
    part = jnp.sum(pt * w)
    # 3) finalize the quadrature sum across ensemble ranks (and any other
    #    spatial axes the caller psums over outside).
    return jax.lax.psum(part, ens_axis)
