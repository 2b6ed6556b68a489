"""Spherical diffusion noise (paper B.7) and the power-law spectrum."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import sht as shtlib

# Table 1 length scales.
FCN3_KT_SCALES = (3.08e-5, 1.23e-4, 4.93e-4, 1.97e-3,
                  7.89e-3, 3.16e-2, 1.26e-1, 5.05e-1)


def power_law_sigma_l(lmax: int, slope: float = 3.0, peak_l: int = 4,
                      band_limit: float = 0.85) -> np.ndarray:
    """(L,) per-degree std of an atmospheric power-law spectrum.

    PSD ~ l^-slope beyond the synoptic peak ``peak_l`` (Tulloch & Smith
    2006), band-limited below ``band_limit * lmax`` (equiangular quadrature
    is inexact near l ~ lmax; power injected there aliases across the whole
    spectrum), and normalized so a field sampled with these per-degree stds
    has unit pointwise variance:  Var = sum_l sigma_l^2 (2l+1) / (4 pi).
    The synthetic-ERA5 surrogate's spectrum.
    """
    ell = np.arange(lmax, dtype=np.float64)
    s = (1.0 + (ell / peak_l) ** slope) ** -1.0
    s[0] = 0.0
    s[ell > band_limit * lmax] = 0.0
    var = (s * (2 * ell + 1) / (4 * np.pi)).sum()
    return np.sqrt(s / var).astype(np.float32)


def sample_spectral_coeffs(key: jax.Array, batch_shape: tuple[int, ...],
                           sigma_l: jax.Array, lmax: int, mmax: int
                           ) -> jax.Array:
    """White orthonormal-basis SH coefficients scaled per degree.

    Real-field convention: m = 0 coefficients are real N(0,1); m > 0 are
    complex with Re, Im ~ N(0, 1/2) (so that the m<0 mirror restores unit
    total variance per (l, m) pair).  ``sigma_l`` has shape (..., L) and is
    broadcast against ``batch_shape + (L, M)`` from the right, so a bank of
    processes passes (n_proc, L) with ``batch_shape`` ending in n_proc.

    Returns (*batch_shape, L, M) complex64.
    """
    shape = batch_shape + (lmax, mmax)
    kr, ki = jax.random.split(key)
    re = jax.random.normal(kr, shape, jnp.float32)
    im = jax.random.normal(ki, shape, jnp.float32)
    m = jnp.arange(mmax)
    scale_m = jnp.where(m == 0, 1.0, np.sqrt(0.5))
    im_mask = jnp.where(m == 0, 0.0, 1.0)
    mask = jnp.asarray(shtlib.mode_mask(lmax, mmax), jnp.float32)
    eta = jax.lax.complex(re * scale_m, im * scale_m * im_mask) * mask
    return eta * sigma_l[..., :, None]


@dataclasses.dataclass(frozen=True)
class SphericalDiffusion:
    """A bank of spherical AR(1) diffusion processes sharing one SHT."""

    sht: shtlib.SHT
    k_t: tuple[float, ...] = FCN3_KT_SCALES
    lam: float = 1.0
    sigma: float = 1.0

    @property
    def n_proc(self) -> int:
        return len(self.k_t)

    def _sigma_l(self) -> np.ndarray:
        """(n_proc, L) spectral standard deviations, eq. (28b)-(28c)."""
        lmax = self.sht.lmax
        l = np.arange(lmax, dtype=np.float64)
        phi = np.exp(-self.lam)
        out = np.zeros((self.n_proc, lmax))
        for i, kt in enumerate(self.k_t):
            e = np.exp(-kt * l * (l + 1.0))
            denom = ((2.0 * l + 1.0) * e)[1:].sum()  # sum over l > 0
            f0 = self.sigma * np.sqrt(2.0 * np.pi * (1.0 - phi * phi)
                                      / max(denom, 1e-30))
            out[i] = f0 * np.sqrt(e)
        out[:, 0] = 0.0  # l = 0: no mean offset, matches sum_{l>0} in (28c)
        return out

    def sigma_l(self) -> jax.Array:
        return jnp.asarray(self._sigma_l(), jnp.float32)

    def _sample_coeffs(self, key: jax.Array, batch_shape: tuple[int, ...],
                       sigma_l: jax.Array) -> jax.Array:
        """White coefficients for the process bank, (*batch, n_proc, L, M)."""
        return sample_spectral_coeffs(key, batch_shape + (self.n_proc,),
                                      sigma_l, self.sht.lmax, self.sht.mmax)

    def init_state(self, key: jax.Array, batch_shape: tuple[int, ...] = ()
                   ) -> jax.Array:
        """Stationary sample of coefficients z_hat: (*batch, n_proc, L, M)."""
        phi = np.exp(-self.lam)
        stat = 1.0 / np.sqrt(max(1.0 - phi * phi, 1e-12))
        return self._sample_coeffs(key, batch_shape, self.sigma_l()) * stat

    def step(self, key: jax.Array, z_hat: jax.Array) -> jax.Array:
        """One AR(1) update in coefficient space, eq. (27)."""
        phi = np.exp(-self.lam)
        eta = self._sample_coeffs(key, z_hat.shape[:-3], self.sigma_l())
        return phi * z_hat + eta

    def to_grid(self, z_hat: jax.Array, pct: jax.Array | None = None
                ) -> jax.Array:
        """Coefficients -> (*batch, n_proc, H, W) real fields."""
        return self.sht.inverse(z_hat, pct)


def center_noise(z: jax.Array, axis: int = 0) -> jax.Array:
    """Antithetic centering (paper E.3): odd members get the negated
    noise of the preceding even member."""
    n = z.shape[axis]
    idx = jnp.arange(n)
    sign = jnp.where(idx % 2 == 0, 1.0, -1.0).astype(z.dtype)
    zt = jnp.take(z, (idx // 2) * 2, axis=axis)
    shape = [1] * zt.ndim
    shape[axis] = n
    return zt * sign.reshape(shape)
