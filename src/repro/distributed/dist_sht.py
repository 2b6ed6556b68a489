"""Distributed SHT via pencil decomposition (paper G.2.2, Algorithm 1).

The paper's distributed transposes map 1:1 onto ``jax.lax.all_to_all`` with
``tiled=True`` inside ``shard_map``: each transpose trades a sharded spatial
axis for a sharded channel axis so the FFT (longitude) and the Legendre GEMM
(latitude) always run on rank-local, contiguous data:

  x (B, C, Hloc, Wloc)
   --all_to_all(lon: C->Cloc, gather W)-->   (B, Cw, Hloc, W)
   --local rFFT, truncate to mmax-->         (B, Cw, Hloc, M)
   --all_to_all(lon: scatter M, C back)-->   (B, C, Hloc, Mloc)
   --all_to_all(lat: C->Ch, gather H)-->     (B, Ch, H, Mloc)
   --local Legendre contraction-->           (B, Ch, L, Mloc)
   --all_to_all(lat: scatter L, C back)-->   (B, C, Lloc, Mloc)

All functions are *rank-local* bodies intended to be called inside
``shard_map`` with the given axis names; channel counts must be divisible by
the corresponding axis sizes (the paper instead tracks ragged split shapes;
we keep channels padded/divisible, which the FCN3 embedding dims satisfy).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp



def _a2a(x, axis_name, split_axis, concat_axis):
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def dist_sht_forward(x: jax.Array, wpct_local: jax.Array, mmax: int,
                     lat_axis: str, lon_axis: str) -> jax.Array:
    """Rank-local body of the forward SHT.

    x: (..., C, Hloc, Wloc) local block of the input signal.
    wpct_local: the *full-latitude* order-major Legendre table sliced to
      this rank's longitudinal mode block: (Mloc, H, L).
    Returns (..., C, Lloc, Mloc) local coefficient block.
    """
    w_total = x.shape[-1] * jax.lax.axis_size(lon_axis)
    # 1) gather longitudes, scatter channels (pencil 1)
    xt = _a2a(x, lon_axis, x.ndim - 3, x.ndim - 1)     # (.., Cw, Hloc, W)
    # 2) local FFT + mode truncation
    xf = jnp.fft.rfft(xt.astype(jnp.float32), axis=-1)[..., :mmax]
    xf = xf * (2.0 * jnp.pi / w_total)
    # 3) scatter modes, gather channels back
    xf = _a2a(xf, lon_axis, xf.ndim - 1, xf.ndim - 3)  # (.., C, Hloc, Mloc)
    # 4) gather latitudes, scatter channels (pencil 2)
    xf = _a2a(xf, lat_axis, xf.ndim - 3, xf.ndim - 2)  # (.., Ch, H, Mloc)
    # 5) local Legendre-Gauss contraction
    re = jnp.einsum("...hm,mhl->...lm", jnp.real(xf), wpct_local)
    im = jnp.einsum("...hm,mhl->...lm", jnp.imag(xf), wpct_local)
    c = jax.lax.complex(re, im)
    # 6) scatter degrees, gather channels back
    return _a2a(c, lat_axis, c.ndim - 2, c.ndim - 3)   # (.., C, Lloc, Mloc)


def dist_sht_inverse(c: jax.Array, pct_local: jax.Array, nlon: int,
                     lat_axis: str, lon_axis: str) -> jax.Array:
    """Rank-local body of the inverse SHT.

    c: (..., C, Lloc, Mloc); pct_local: (Mloc, L, H), order-major.
    Returns (..., C, Hloc, Wloc).
    """
    mmax_local = c.shape[-1]
    n_lon_ranks = jax.lax.axis_size(lon_axis)
    # 1) gather degrees, scatter channels
    ct = _a2a(c, lat_axis, c.ndim - 3, c.ndim - 2)     # (.., Ch, L, Mloc)
    # 2) local inverse Legendre
    hi = jax.lax.Precision.HIGHEST
    sr = jnp.einsum("...lm,mlh->...hm", jnp.real(ct), pct_local,
                    precision=hi)
    si = jnp.einsum("...lm,mlh->...hm", jnp.imag(ct), pct_local,
                    precision=hi)
    s = jax.lax.complex(sr, si)
    # 3) scatter latitudes, gather channels
    s = _a2a(s, lat_axis, s.ndim - 2, s.ndim - 3)      # (.., C, Hloc, Mloc)
    # 4) gather modes, scatter channels
    s = _a2a(s, lon_axis, s.ndim - 3, s.ndim - 1)      # (.., Cw, Hloc, M)
    pad = nlon // 2 + 1 - s.shape[-1]
    if pad:
        s = jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, pad)])
    u = jnp.fft.irfft(s, n=nlon, axis=-1) * nlon
    # 5) scatter longitudes, gather channels back
    return _a2a(u, lon_axis, u.ndim - 1, u.ndim - 3)   # (.., C, Hloc, Wloc)
