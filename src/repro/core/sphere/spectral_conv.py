"""Global spherical convolutions via the convolution theorem (paper B.4).

The convolution theorem on the sphere, eq. (19), states that an axisymmetric
filter acts diagonally in spherical-harmonic space:
``(u (x) k)_l^m = u_l^m * k_l^0``.  Following SFNO (Bonev et al. 2023), the
filter is *parameterized* directly in the spectral domain.  Two variants:

* ``depthwise`` — a real per-(channel, l) gain, the literal convolution
  theorem (strictly rotation-equivariant under SO(3)/SO(2)).
* ``full`` — complex per-l channel-mixing weights (the SFNO parameterization);
  trades strict equivariance for capacity, which FCN3 uses in its two global
  processor blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sphere import sht as shtlib
from repro.kernels.config import KernelConfig


def init_spectral_filter(key: jax.Array, c_out: int, c_in: int, lmax: int,
                         mode: str = "full", dtype=jnp.float32) -> dict:
    """He-style init scaled so output variance matches input (paper C.6)."""
    if mode == "depthwise":
        if c_out != c_in:
            raise ValueError("depthwise spectral filter requires c_out == c_in")
        w = jnp.ones((c_in, lmax), dtype)
        return {"w": w}
    scale = np.sqrt(1.0 / max(c_in, 1))
    kr, ki = jax.random.split(key)
    return {
        "w_re": scale * jax.random.normal(kr, (c_out, c_in, lmax), dtype),
        "w_im": scale * jax.random.normal(ki, (c_out, c_in, lmax), dtype),
    }


def transforms(sht_buffers: dict, nlon: int,
               kernels: KernelConfig | None = None) -> tuple:
    """The forward and inverse SHT of ``sht_buffers``, as ``(fwd, inv)``:
    ``fwd(x)`` (..., H, W) -> (..., L, M) on the grid of
    ``sht_buffers["wpct"]`` and ``inv(c)`` (..., L, M) -> (..., H', nlon)
    on the grid of ``sht_buffers["pct"]`` (the two tables may lie on
    different grids of the same L and M: a transform pair that
    resamples).  ``kernels`` routes the Legendre stage through the
    Pallas substrate; None keeps the reference path."""
    if kernels is not None and kernels.resolve("sht")[0] == "pallas":
        from repro.kernels import dispatch as kdispatch
        fwd = lambda x_: kdispatch.sht_forward(  # noqa: E731
            x_, sht_buffers["wpct"], kernels)
        inv = lambda c_: kdispatch.sht_inverse(  # noqa: E731
            c_, sht_buffers["pct"], nlon, kernels)
    else:
        fwd = lambda x_: shtlib.sht_forward(x_, sht_buffers["wpct"])  # noqa: E731
        inv = lambda c_: shtlib.sht_inverse(c_, sht_buffers["pct"], nlon)  # noqa: E731
    return fwd, inv


def spectral_mix(params: dict, c: jax.Array) -> jax.Array:
    """(..., C_in, L, M) coefficients -> (..., C_out, L, M) under the
    filter of ``init_spectral_filter``: a real per-(channel, l) gain
    (``depthwise``) or the complex per-l channel mix
    ``y[o, l, m] = sum_i W[o, i, l] c[i, l, m]`` (``full``)."""
    if "w" in params:  # depthwise, real gain
        return c * params["w"][..., :, None]
    # Complex spectral weights always combine in fp32 (the
    # coefficients c are complex64).  Stacking c's real and imaginary
    # parts makes the complex product two real contractions in which
    # each (Co, Ci, L) weight appears once, so a TPU holds one
    # rounded copy per weight -- hoisted out of the rollout loop, at
    # 721x1440 each is 0.3 GB -- where XLA's own complex lowering
    # holds three per block.
    m = c.shape[-1]
    c2 = jnp.concatenate([jnp.real(c), jnp.imag(c)], axis=-1)
    p = jnp.einsum("oil,...ilm->...olm",
                   params["w_re"].astype(jnp.float32), c2)
    q = jnp.einsum("oil,...ilm->...olm",
                   params["w_im"].astype(jnp.float32), c2)
    return jax.lax.complex(p[..., :m] - q[..., m:], p[..., m:] + q[..., :m])


def apply_spectral_conv(params: dict, x: jax.Array, sht_buffers: dict,
                        nlon: int, lmax_keep: int | None = None,
                        kernels: KernelConfig | None = None) -> jax.Array:
    """x: (..., C, H, W) -> (..., C_out, H, W) through the spectral domain.

    Args:
      params: from ``init_spectral_filter``.
      x: input signal, channels-second-to-last-but-two layout (..., C, H, W).
      sht_buffers: {"wpct": (M,H,L), "pct": (M,L,H)} order-major Legendre
        tables (``SHT.buffers``).
      nlon: output longitude count (== W).
      lmax_keep: optional hard spectral truncation (anti-aliasing).
      kernels: substrate selection for the two SHTs (the hot Legendre
        GEMMs); None keeps the reference path.
    """
    fwd, inv = transforms(sht_buffers, nlon, kernels)
    c = fwd(x)  # (..., C, L, M)
    if lmax_keep is not None and lmax_keep < c.shape[-2]:
        keep = c[..., :lmax_keep, :]
        c = jnp.pad(keep, [(0, 0)] * (c.ndim - 2)
                    + [(0, c.shape[-2] - lmax_keep), (0, 0)])
    return inv(spectral_mix(params, c))
