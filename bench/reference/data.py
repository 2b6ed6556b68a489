"""Family-neutral data of the benchmark: the synthetic ERA5 surrogate
that every family's reference rolls from, and the area-weighted products
served per lead (paper Appendix D, F.7).

``SyntheticERA5(cfg, sht)`` needs of ``cfg`` only ``n_state`` (channels
of the state) and ``water_channel_indices()`` (the channels kept
positive); ``sht`` is the reference's transform on the model's input
grid.  Nothing here names a model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import noise as noiselib
from . import sht as shtlib


# ---------------------------------------------------------------------------
# Synthetic ERA5: the initial conditions, forcings and truth
# ---------------------------------------------------------------------------

class SyntheticERA5:
    """Deterministic spectral surrogate of the ``cfg.n_state``-channel
    state: a band-limited power law (PSD ~ l^-3 beyond l = 4), AR(1) in
    time with rho = 0.95 per 6 h, a zonal climatology offset, softplus on
    water channels; aux = land/sea masks, orography, cosine zenith
    angle."""

    def __init__(self, cfg, sht: shtlib.SHT):
        self.cfg = cfg
        self.sht = sht
        self.grid = sht.grid
        self.sigma_l = noiselib.power_law_sigma_l(sht.lmax, 3.0, 4)
        g = self.grid
        lat = np.pi / 2 - g.colat[:, None]
        lon = g.lons[None, :]
        conts = (np.sin(2 * lat) * np.cos(3 * lon)
                 + 0.5 * np.sin(5 * lat + 1.3) * np.sin(2 * lon + 0.7))
        land = (conts > 0.15).astype(np.float32)
        oro = np.maximum(conts - 0.15, 0.0).astype(np.float32) * 2.0
        self.static_aux = np.stack([land, 1.0 - land, oro]).astype(np.float32)

    def aux_fields(self, t_hours: float) -> np.ndarray:
        g = self.grid
        day = t_hours / 24.0
        decl = np.deg2rad(23.44) * np.sin(2 * np.pi * (day - 81.0) / 365.25)
        lat = np.pi / 2 - g.colat
        ha = (t_hours % 24.0) / 24.0 * 2 * np.pi + g.lons[None, :] - np.pi
        cz = (np.sin(lat)[:, None] * np.sin(decl)
              + np.cos(lat)[:, None] * np.cos(decl) * np.cos(ha))
        cz = np.maximum(cz, 0.0).astype(np.float32)
        return np.concatenate([self.static_aux, cz[None]], axis=0)

    def _field(self, key, pct):
        lmax, mmax = self.sht.lmax, self.sht.mmax
        kr, ki = jax.random.split(key)
        shape = (self.cfg.n_state, lmax, mmax)
        re = jax.random.normal(kr, shape)
        im = jax.random.normal(ki, shape)
        m = jnp.arange(mmax)
        im = jnp.where(m == 0, 0.0, im) * np.sqrt(0.5)
        re = re * jnp.where(m == 0, 1.0, np.sqrt(0.5))
        mask = jnp.asarray(shtlib.mode_mask(lmax, mmax), jnp.float32)
        c = (jax.lax.complex(re, im) * mask
             * jnp.asarray(self.sigma_l)[:, None])
        return self.sht.inverse(c, pct)

    def state(self, sample: int, t: int = 0, pct: jax.Array | None = None
              ) -> jax.Array:
        """(C, H, W) state of ``sample`` at 6-hour offset ``t``; pass the
        inverse table ``pct`` under ``jit``."""
        cfg = self.cfg
        base = jax.random.fold_in(jax.random.PRNGKey(20200101), sample)
        x = self._field(jax.random.fold_in(base, 0), pct)
        rho = 0.95
        for k in range(1, t + 1):
            x = (rho * x + np.sqrt(1 - rho * rho)
                 * self._field(jax.random.fold_in(base, k), pct))
        colat = jnp.asarray(self.grid.colat, jnp.float32)
        chan = jnp.arange(cfg.n_state, dtype=jnp.float32)
        x = x + (0.5 * jnp.cos(colat)[None, :, None]
                 * jnp.cos(chan * 0.37)[:, None, None])
        water = np.zeros((cfg.n_state,), bool)
        water[cfg.water_channel_indices()] = True
        return jnp.where(jnp.asarray(water)[:, None, None],
                         jax.nn.softplus(x), x)


# ---------------------------------------------------------------------------
# Served products (paper Appendix D, F.7)
# ---------------------------------------------------------------------------

def _spatial_mean(x, aw):
    return jnp.einsum("...hw,hw->...", x, aw) / jnp.sum(aw)


def scores(ens: jax.Array, truth: jax.Array | None, aw: jax.Array,
           wpct: jax.Array | None) -> dict[str, jax.Array]:
    """Per-channel products of one lead: fair CRPS, ensemble-mean RMSE,
    spread, spread-skill ratio (with the sqrt((E+1)/E) correction) and,
    with ``wpct``, the member-mean angular power spectrum (C, L)."""
    out = {}
    e = ens.shape[0]
    if truth is not None:
        err = jnp.mean(jnp.abs(ens - truth[None]), axis=0)
        pair = jnp.mean(jnp.abs(ens[:, None] - ens[None, :]), axis=(0, 1))
        out["crps"] = _spatial_mean(err - 0.5 * e / (e - 1.0) * pair, aw)
        skill = jnp.sqrt(_spatial_mean((ens.mean(0) - truth) ** 2, aw))
        spread = jnp.sqrt(_spatial_mean(jnp.var(ens, axis=0, ddof=1), aw))
        out["ens_rmse"] = skill
        out["spread"] = spread
        out["ssr"] = jnp.sqrt((e + 1.0) / e) * spread / skill
    if wpct is not None:
        out["spectrum"] = jnp.mean(
            shtlib.spectrum(shtlib.sht_forward(ens, wpct)), axis=0)
    return out
