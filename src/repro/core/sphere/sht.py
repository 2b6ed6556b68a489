"""Spherical harmonic transforms (paper Appendix B.3).

The SHT decomposes into an FFT along longitude and a Legendre contraction
(GEMM) along latitude (Schaeffer 2013), exactly the structure distributed in
the paper's Algorithm 1 and the structure our Pallas ``legendre`` kernel
accelerates on TPU.

Conventions
-----------
* Real input fields ``x`` of shape (..., nlat, nlon).
* Coefficients ``c`` of shape (..., lmax, mmax) complex64, orders m >= 0 only
  (real fields: c_l^{-m} = (-1)^m conj(c_l^m)).
* Orthonormal spherical harmonics: forward is
  ``c_l^m = sum_h w_h Pbar[h,l,m] * (2 pi / nlon) * rfft(x)[h, m]``
  and the inverse uses the Hermitian-symmetric irfft, so
  ``isht(sht(x)) == x`` exactly for band-limited signals on Gaussian grids.

All functions are pure; the precomputed Legendre tables are passed in as
arrays ("buffers"), never captured as constants, so they can be donated,
sharded and replaced by ``ShapeDtypeStruct`` in compile-only dry-runs.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sphere import fourier
from repro.core.sphere import grids as glib
from repro.core.sphere import legendre as leg


def sht_forward(x: jax.Array, wpct: jax.Array) -> jax.Array:
    """Forward SHT. x: (..., H, W) real -> (..., L, M) complex.

    Args:
      x: input signal.
      wpct: (M, H, L) quadrature-weighted Legendre table
        ``w_h * Pbar_l^m(cos theta_h)``, order-major.
    """
    m = wpct.shape[0]
    w = x.shape[-1]
    xf = fourier.rfft(x.astype(jnp.float32), axis=-1)[..., :m]
    xf = xf * (2.0 * jnp.pi / w)
    # Legendre contraction over latitude: (..., H, M) x (M, H, L) -> (..., L, M)
    re = jnp.einsum("...hm,mhl->...lm", jnp.real(xf), wpct)
    im = jnp.einsum("...hm,mhl->...lm", jnp.imag(xf), wpct)
    return jax.lax.complex(re, im)


def sht_inverse(c: jax.Array, pct: jax.Array, nlon: int) -> jax.Array:
    """Inverse SHT. c: (..., L, M) complex -> (..., H, nlon) real.

    Args:
      c: spherical harmonic coefficients (orders m >= 0).
      pct: (M, L, H) unweighted Legendre table ``Pbar_l^m(cos theta_h)``,
        order-major.
      nlon: number of output longitudes.

    Contracted at float32 ("highest"): in this layout and precision a
    TPU reads the table where it lies, with no transposed or bf16 copy
    (a rollout's loop would hoist such a copy of the 1.5 GB IO-grid
    table and hold it for all of the loop).
    """
    hi = jax.lax.Precision.HIGHEST
    sr = jnp.einsum("...lm,mlh->...hm", jnp.real(c), pct, precision=hi)
    si = jnp.einsum("...lm,mlh->...hm", jnp.imag(c), pct, precision=hi)
    return synthesize(jax.lax.complex(sr, si), nlon)


def synthesize(spec: jax.Array, nlon: int) -> jax.Array:
    """(..., H, M) Legendre-synthesized orders -> (..., H, nlon) field."""
    m = spec.shape[-1]
    pad = nlon // 2 + 1 - m
    if pad < 0:
        raise ValueError(f"mmax={m} too large for nlon={nlon}")
    if pad:
        spec = jnp.pad(spec, [(0, 0)] * (spec.ndim - 1) + [(0, pad)])
    # irfft contributes 1/nlon and the Hermitian double-count of m>0 modes.
    return fourier.irfft(spec, n=nlon, axis=-1) * nlon


_TABLES: dict[tuple, jax.Array] = {}
_TABLES_LOCK = threading.Lock()


@dataclasses.dataclass(frozen=True)
class SHT:
    """Precomputed SHT for one grid; thin wrapper around the pure functions."""

    grid: glib.SphereGrid
    lmax: int
    mmax: int
    dtype: jnp.dtype = jnp.float32

    @classmethod
    def create(cls, grid: glib.SphereGrid, lmax: int | None = None,
               mmax: int | None = None, dtype=jnp.float32) -> "SHT":
        lmax = int(lmax if lmax is not None else grid.nlat)
        mmax = int(mmax if mmax is not None else min(lmax, grid.nlon // 2 + 1))
        return cls(grid=grid, lmax=lmax, mmax=mmax, dtype=dtype)

    def table(self, name: str) -> jax.Array:
        """One Legendre table as a device array, in the order-major
        layout both the reference einsums and the Pallas Legendre kernel
        contract: "wpct" (M, H, L) for the forward transform, "pct"
        (M, L, H) for the inverse.

        One device copy per (grid, lmax, mmax, dtype, table) is shared
        by every ``SHT`` of that shape -- at 721x1440 each table is
        1.5 GB, and the model's noise process, the data source and the
        losses all transform on the IO grid -- and built only when some
        caller needs it."""
        g = self.grid
        key = (g.nlat, g.nlon, g.kind, self.lmax, self.mmax,
               jnp.dtype(self.dtype).name, name)
        with _TABLES_LOCK:
            hit = _TABLES.get(key)
            if hit is None:
                pbar = leg.cached_legendre_table(self.lmax, self.mmax,
                                                 g.colat)   # (H, L, M)
                if name == "wpct":
                    host = (pbar * g.quad_weights[:, None, None]
                            ).transpose(2, 0, 1)
                elif name == "pct":
                    host = pbar.transpose(2, 1, 0)
                else:
                    raise KeyError(f"no Legendre table {name!r}")
                hit = jnp.asarray(host, self.dtype)
                _TABLES[key] = hit
            return hit

    def buffers(self) -> dict[str, jax.Array]:
        """Legendre tables as arrays (pass through the model as buffers)."""
        return {"wpct": self.table("wpct"), "pct": self.table("pct")}

    def buffer_specs(self) -> dict[str, jax.ShapeDtypeStruct]:
        h, l, m = self.grid.nlat, self.lmax, self.mmax
        return {
            "wpct": jax.ShapeDtypeStruct((m, h, l), self.dtype),
            "pct": jax.ShapeDtypeStruct((m, l, h), self.dtype),
        }

    def forward(self, x: jax.Array, buffers: dict | None = None) -> jax.Array:
        wpct = buffers["wpct"] if buffers is not None else self.table("wpct")
        return sht_forward(x, wpct)

    def inverse(self, c: jax.Array, buffers: dict | None = None) -> jax.Array:
        pct = buffers["pct"] if buffers is not None else self.table("pct")
        return sht_inverse(c, pct, self.grid.nlon)


def resample(x: jax.Array, sht_in: SHT, sht_out: SHT) -> jax.Array:
    """Alias-free spectral resampling between grids (paper B.6, SHT variant)."""
    c = sht_in.forward(x)
    l = min(sht_in.lmax, sht_out.lmax)
    m = min(sht_in.mmax, sht_out.mmax)
    c = c[..., :l, :m]
    pad_l = sht_out.lmax - l
    pad_m = sht_out.mmax - m
    c = jnp.pad(c, [(0, 0)] * (c.ndim - 2) + [(0, pad_l), (0, pad_m)])
    return sht_out.inverse(c)


def spectrum(c: jax.Array) -> jax.Array:
    """Angular power spectral density, paper eq. (53): sum_m |c_l^m|^2.

    Accounts for the Hermitian double count of m>0 orders of real fields.
    c: (..., L, M) -> (..., L).
    """
    p = jnp.abs(c) ** 2
    mult = jnp.concatenate(
        [jnp.ones((1,), p.dtype), 2.0 * jnp.ones((p.shape[-1] - 1,), p.dtype)]
    )
    return jnp.einsum("...lm,m->...l", p, mult)


def mode_mask(lmax: int, mmax: int) -> np.ndarray:
    """(L, M) boolean mask of valid (m <= l) coefficient slots."""
    l = np.arange(lmax)[:, None]
    m = np.arange(mmax)[None, :]
    return m <= l
