"""Spherical harmonic transforms: longitude FFT + Legendre contraction.

Coefficients are (..., L, M) complex64 with orders m >= 0; orthonormal
harmonics, forward ``c = sum_h w_h Pbar (2 pi / W) rfft(x)``, inverse by
the Hermitian-symmetric irfft.  Tables are order-major: "wpct" (M, H, L)
quadrature-weighted, "pct" (M, L, H).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import fourier
from . import grids as glib
from . import legendre as leg


def sht_forward(x: jax.Array, wpct: jax.Array) -> jax.Array:
    """x: (..., H, W) real -> (..., L, M) complex."""
    m = wpct.shape[0]
    w = x.shape[-1]
    xf = fourier.rfft(x.astype(jnp.float32), axis=-1)[..., :m]
    xf = xf * (2.0 * jnp.pi / w)
    re = jnp.einsum("...hm,mhl->...lm", jnp.real(xf), wpct)
    im = jnp.einsum("...hm,mhl->...lm", jnp.imag(xf), wpct)
    return jax.lax.complex(re, im)


def sht_inverse(c: jax.Array, pct: jax.Array, nlon: int) -> jax.Array:
    """c: (..., L, M) complex -> (..., H, nlon) real."""
    hi = jax.lax.Precision.HIGHEST
    sr = jnp.einsum("...lm,mlh->...hm", jnp.real(c), pct, precision=hi)
    si = jnp.einsum("...lm,mlh->...hm", jnp.imag(c), pct, precision=hi)
    spec = jax.lax.complex(sr, si)
    pad = nlon // 2 + 1 - spec.shape[-1]
    if pad:
        spec = jnp.pad(spec, [(0, 0)] * (spec.ndim - 1) + [(0, pad)])
    return fourier.irfft(spec, n=nlon, axis=-1) * nlon


@dataclasses.dataclass(frozen=True)
class SHT:
    """One grid's transform; its two tables are built on first use."""

    grid: glib.SphereGrid
    lmax: int
    mmax: int

    @classmethod
    def create(cls, grid: glib.SphereGrid) -> "SHT":
        lmax = int(grid.nlat)
        return cls(grid=grid, lmax=lmax, mmax=min(lmax, grid.nlon // 2 + 1))

    def table(self, name: str) -> jax.Array:
        cache = self.__dict__.setdefault("_tables", {})
        if name not in cache:
            g = self.grid
            pbar = leg.cached_legendre_table(self.lmax, self.mmax, g.colat)
            if name == "wpct":
                host = (pbar * g.quad_weights[:, None, None]).transpose(2, 0, 1)
            elif name == "pct":
                host = pbar.transpose(2, 1, 0)
            else:
                raise KeyError(name)
            cache[name] = jnp.asarray(host, jnp.float32)
        return cache[name]

    def buffers(self) -> dict[str, jax.Array]:
        return {"wpct": self.table("wpct"), "pct": self.table("pct")}

    def forward(self, x: jax.Array, wpct: jax.Array | None = None
                ) -> jax.Array:
        return sht_forward(x, self.table("wpct") if wpct is None else wpct)

    def inverse(self, c: jax.Array, pct: jax.Array | None = None
                ) -> jax.Array:
        return sht_inverse(c, self.table("pct") if pct is None else pct,
                           self.grid.nlon)


def spectrum(c: jax.Array) -> jax.Array:
    """Angular power per degree, sum_m |c_l^m|^2 with m > 0 counted twice."""
    p = jnp.abs(c) ** 2
    mult = jnp.concatenate(
        [jnp.ones((1,), p.dtype), 2.0 * jnp.ones((p.shape[-1] - 1,), p.dtype)])
    return jnp.einsum("...lm,m->...l", p, mult)


def mode_mask(lmax: int, mmax: int) -> np.ndarray:
    """(L, M) mask of valid (m <= l) coefficient slots."""
    return np.arange(mmax)[None, :] <= np.arange(lmax)[:, None]
