"""Bilinear interpolation of spherical signals (paper B.6, eqs. 25-26).

Precomputes gather indices and weights (NumPy, config time) for resampling a
(..., H_in, W_in) signal on one tensor-product grid to another.  Longitude is
periodic; latitudes beyond the first/last ring interpolate against the pole
value, which is defined as the longitudinal mean of the nearest ring
(eq. 26) -- implemented here without materializing extended rows by folding
the 1/W mean into the interpolation weights.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import grids as glib


@dataclasses.dataclass(frozen=True)
class BilinearResample:
    """Precomputed bilinear resampling plan between two spherical grids."""

    grid_in: glib.SphereGrid
    grid_out: glib.SphereGrid
    # latitude neighbours / weights; index -1 / nlat encode poles
    lat_idx0: np.ndarray  # (H_out,) int32 in [-1, H_in-1]
    lat_w: np.ndarray     # (H_out,) float32 weight of idx0+1 neighbour
    lon_idx0: np.ndarray  # (W_out,) int32
    lon_w: np.ndarray     # (W_out,) float32

    @classmethod
    def create(cls, grid_in: glib.SphereGrid, grid_out: glib.SphereGrid):
        ti, to = grid_in.colat, grid_out.colat
        # latitude: find interval; allow virtual pole rows at theta=0, pi.
        idx0 = np.searchsorted(ti, to, side="right") - 1  # in [-1, H_in-1]
        idx0 = np.clip(idx0, -1, ti.shape[0] - 1)
        t0 = np.where(idx0 >= 0, ti[np.clip(idx0, 0, None)], 0.0)
        idx1 = idx0 + 1
        t1 = np.where(idx1 <= ti.shape[0] - 1,
                      ti[np.clip(idx1, None, ti.shape[0] - 1)], np.pi)
        denom = np.where(t1 > t0, t1 - t0, 1.0)
        w = np.clip((to - t0) / denom, 0.0, 1.0)

        pi_, po = grid_in.lons, grid_out.lons
        dphi = 2.0 * np.pi / grid_in.nlon
        j0 = np.floor(po / dphi).astype(np.int64)
        wl = (po - j0 * dphi) / dphi
        j0 = j0 % grid_in.nlon
        return cls(
            grid_in=grid_in, grid_out=grid_out,
            lat_idx0=idx0.astype(np.int32), lat_w=w.astype(np.float32),
            lon_idx0=j0.astype(np.int32), lon_w=wl.astype(np.float32),
        )

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: (..., H_in, W_in) -> (..., H_out, W_out)."""
        hin = self.grid_in.nlat
        # Longitudinal interpolation first (cheap, periodic).
        j0 = jnp.asarray(self.lon_idx0)
        j1 = (j0 + 1) % self.grid_in.nlon
        wl = jnp.asarray(self.lon_w)
        xl = x[..., :, j0] * (1.0 - wl) + x[..., :, j1] * wl  # (..., H_in, W_out)

        # Pole rows: longitudinal mean of nearest ring (area-weighted; uniform
        # lon spacing => plain mean), broadcast over W_out.
        north = jnp.mean(x[..., 0, :], axis=-1, keepdims=True)
        south = jnp.mean(x[..., hin - 1, :], axis=-1, keepdims=True)
        ones = jnp.ones((1, xl.shape[-1]), xl.dtype)
        xl = jnp.concatenate(
            [north[..., None, :] * ones, xl, south[..., None, :] * ones],
            axis=-2,
        )  # (..., H_in + 2, W_out); row 0 = north pole, row H_in+1 = south.

        i0 = jnp.asarray(self.lat_idx0) + 1  # shift for the prepended pole row
        i1 = i0 + 1
        wt = jnp.asarray(self.lat_w)[:, None]
        return (jnp.take(xl, i0, axis=-2) * (1.0 - wt)
                + jnp.take(xl, i1, axis=-2) * wt)
