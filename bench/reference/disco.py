"""DISCO convolutions on the sphere (paper Appendix B.4), FFT path only.

A plan holds the quadrature-weighted Morlet filter values ``psi``
(K, H_out, S, W_in) over a band of S input rings per output ring; the
convolution correlates each ring band with the filter along longitude
(FFT), then mixes basis responses and channels with learned weights.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import fourier
from . import grids as glib


# ---------------------------------------------------------------------------
# Filter basis
# ---------------------------------------------------------------------------

def morlet_basis_spec(ell_max: int = 2, m_max: int = 2) -> list[tuple[int, int, str]]:
    """Enumerate the real Morlet basis: (l, m, 'cos'|'sin') triples.

    sin(0,0) is identically zero and excluded. Default (2,2) -> 7 functions.
    """
    spec = []
    for l in range(ell_max):
        for m in range(m_max):
            spec.append((l, m, "cos"))
            if not (l == 0 and m == 0):
                spec.append((l, m, "sin"))
    return spec


def eval_morlet_basis(spec, tprime: np.ndarray, alpha: np.ndarray) -> np.ndarray:
    """Evaluate the basis at normalized radius t' in [0,1], orientation alpha.

    Returns (K, *tprime.shape). Values are zero for t' > 1 (outside support).
    Hann window h(t') = cos^2(pi/2 t') ensures smooth compact support.
    """
    inside = (tprime <= 1.0).astype(np.float64)
    h = np.cos(0.5 * np.pi * np.clip(tprime, 0.0, 1.0)) ** 2 * inside
    out = np.zeros((len(spec),) + tprime.shape, dtype=np.float64)
    for i, (l, m, kind) in enumerate(spec):
        phase = np.pi * tprime * (l * np.sin(alpha) + m * np.cos(alpha))
        osc = np.cos(phase) if kind == "cos" else np.sin(phase)
        out[i] = h * osc
    return out


# ---------------------------------------------------------------------------
# psi tensor construction
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DiscoPlan:
    """Filter geometry between two grids.

    psi[k, h, s, dw] multiplies u[lat_idx[h, s], (w*stride + dw) % W_in];
    ``affine`` = (a, b) with lat_idx[h, s] == clip(a*h + s + b).
    """

    grid_in: glib.SphereGrid
    grid_out: glib.SphereGrid
    n_basis: int
    theta_cutoff: float
    lat_idx: np.ndarray
    psi: np.ndarray
    stride: int
    affine: tuple[int, int] | None = None
    ell_max: int = 2
    m_max: int = 2
    cutoff_factor: float = 3.0

    def buffers(self) -> dict[str, jax.Array]:
        return {"psi": jnp.asarray(self.psi, jnp.float32),
                "lat_idx": jnp.asarray(self.lat_idx)}


def make_disco_plan(grid_in: glib.SphereGrid, grid_out: glib.SphereGrid,
                    ell_max: int = 2, m_max: int = 2,
                    cutoff_factor: float = 3.0) -> DiscoPlan:
    """theta_cutoff = cutoff_factor * pi / nlat_out."""
    if grid_in.nlon % grid_out.nlon:
        raise ValueError("W_out must divide W_in for strided DISCO")
    return _cached_plan(grid_in.nlat, grid_in.nlon, grid_in.kind,
                        grid_out.nlat, grid_out.nlon, grid_out.kind,
                        ell_max, m_max, cutoff_factor)


@functools.lru_cache(maxsize=8)
def _cached_plan(nlat_in, nlon_in, kind_in, nlat_out, nlon_out, kind_out,
                 ell_max, m_max, cutoff_factor) -> DiscoPlan:
    return _build_plan(glib.make_grid(nlat_in, nlon_in, kind_in),
                       glib.make_grid(nlat_out, nlon_out, kind_out),
                       ell_max, m_max, cutoff_factor)


def _build_plan(grid_in, grid_out, ell_max, m_max, cutoff_factor) -> DiscoPlan:
    spec = morlet_basis_spec(ell_max, m_max)
    k = len(spec)
    cutoff = cutoff_factor * np.pi / grid_out.nlat

    ti = grid_in.colat          # (H_in,)
    to = grid_out.colat         # (H_out,)
    dphi = grid_in.lons         # (W_in,) offsets relative to the output lon
    h_in, w_in = grid_in.nlat, grid_in.nlon
    h_out = grid_out.nlat

    # Latitude band: rows with |theta_o - theta_i| <= cutoff (geodesic
    # distance is >= latitude difference, so this band is sufficient).
    # The band is *affinized*: lat_idx[h, s] = clip(a*h + s + b) with the
    # slope a = row-density ratio, widened so it covers [lo, hi) for every
    # output row (entries outside the true support carry zero psi).  The
    # affine structure lets the convolution gather input rows with strided
    # slices instead of jnp.take -- which GSPMD would answer by replicating
    # the operand (a ~100 TB/step all-gather at FCN3 production scale).
    lo = np.searchsorted(ti, to - cutoff, side="left")
    hi = np.searchsorted(ti, to + cutoff, side="right")
    a = max(1, int(round(h_in / h_out)))
    resid = lo - a * np.arange(h_out)
    b = int(resid.min())
    s = int((hi - a * np.arange(h_out) - b).max())
    raw = a * np.arange(h_out)[:, None] + np.arange(s)[None, :] + b
    lat_idx = np.clip(raw, 0, h_in - 1)
    valid = (raw >= lo[:, None]) & (raw < hi[:, None])
    affine = (a, b)

    # Geometry, vectorized over (H_out, S, W_in).
    t_o = to[:, None, None]
    t_i = ti[lat_idx][:, :, None]
    dph = dphi[None, None, :]
    cosd = (np.cos(t_o) * np.cos(t_i)
            + np.sin(t_o) * np.sin(t_i) * np.cos(dph))
    d = np.arccos(np.clip(cosd, -1.0, 1.0))
    # Bearing of the input point as seen from the output point (from north).
    alpha = np.arctan2(
        np.sin(t_i) * np.sin(dph),
        np.sin(t_o) * np.cos(t_i) - np.cos(t_o) * np.sin(t_i) * np.cos(dph),
    )

    vals = eval_morlet_basis(spec, d / cutoff, alpha)  # (K, H_out, S, W_in)
    # Quadrature weights of the *input* grid (area element per point).
    w_q = grid_in.cell_area[lat_idx][None, :, :, None]
    psi = vals * w_q * valid[None, :, :, None]

    # Per-basis scalar normalization: quadrature-weighted filters have tiny
    # magnitude (~ area of the support disk); rescale each basis function by
    # its mean l1 norm so the *operator* gain is <= ~1 for any input
    # (worst case: spatially smooth fields, where taps add coherently --
    # exactly the regime of autoregressive forecast rollouts; an l2/white
    # normalization amplifies smooth fields by l1/l2 ~ sqrt(support) per
    # layer and blows up rollouts).  Per-k constant => latitude-uniform =>
    # equivariance preserved; absorbed by the learnable weights.
    norms = np.abs(psi).sum(axis=(2, 3)).mean(axis=1)  # (K,)
    norms = np.where(norms > 0, norms, 1.0)
    psi = psi / norms[:, None, None, None]

    return DiscoPlan(
        grid_in=grid_in, grid_out=grid_out, n_basis=k,
        theta_cutoff=float(cutoff), lat_idx=lat_idx.astype(np.int32),
        psi=psi.astype(np.float32), stride=w_in // grid_out.nlon,
        affine=affine, ell_max=int(ell_max), m_max=int(m_max),
        cutoff_factor=float(cutoff_factor),
    )


def _gather_band(x: jax.Array, lat_idx, affine, h_out: int) -> jax.Array:
    """(..., H_in, W) -> (..., H_out, S, W) band of input latitude rows.

    Uses clamp-padded strided slices when the band is affine (GSPMD-safe:
    slices propagate shardings; `jnp.take` over this axis makes the SPMD
    partitioner replicate the whole operand).
    """
    if affine is None:
        return jnp.take(x, jnp.asarray(lat_idx), axis=-2)
    a, b = affine
    s = lat_idx.shape[1]
    h_in = x.shape[-2]
    # clamp-pad so every slice start is in range: rows < 0 clamp to 0,
    # rows >= H_in clamp to H_in-1 (matches the clipped lat_idx).
    lo_pad = max(0, -b)
    hi_pad = max(0, a * (h_out - 1) + (s - 1) + b - (h_in - 1))
    xp = x
    if lo_pad or hi_pad:
        pad = [(0, 0)] * (x.ndim - 2) + [(lo_pad, hi_pad), (0, 0)]
        xp = jnp.pad(x, pad, mode="edge")
    cols = []
    for si in range(s):
        start = b + si + lo_pad
        sl = jax.lax.slice_in_dim(xp, start, start + a * (h_out - 1) + 1,
                                  stride=a, axis=x.ndim - 2)
        cols.append(sl)
    return jnp.stack(cols, axis=-2)                 # (..., H_out, S, W)


def fft_correlate(xg: jax.Array, psi: jax.Array, stride: int) -> jax.Array:
    """Circular correlation of gathered bands with full-circle filters.

    xg: (..., H_out, S, W_in); psi: (K, H_out, S, W_in) ->
    (..., K, H_out, W_in // stride) with
    out[..., k, h, w] = sum_{s, dw} psi[k, h, s, dw] * xg[..., h, s,
                                                       (w*stride + dw) % W_in].
    """
    w_in = xg.shape[-1]
    xf = fourier.rfft(xg.astype(jnp.float32), axis=-1)
    pf = jnp.conj(fourier.rfft(psi.astype(jnp.float32), axis=-1))  # (K,H,S,F)
    # correlation: out_hat = x_hat * conj(psi_hat), summed over the band
    # S.  An explicit sum, not a dot: S is 5-13 rings, and as a dot
    # operand a TPU tiles it to 128 lanes (25x the bytes at 721x1440).
    prod = sum(xf[..., None, :, s, :] * pf[:, :, s, :]
               for s in range(psi.shape[2]))
    out = fourier.irfft(prod, n=w_in, axis=-1)
    if stride > 1:
        out = out[..., ::stride]
    return out


def disco_conv(x: jax.Array, psi: jax.Array, lat_idx: jax.Array,
               stride: int, affine: tuple[int, int] | None = None
               ) -> jax.Array:
    """Raw DISCO contraction via FFT circular correlation.

    x: (..., H_in, W_in) -> (..., K, H_out, W_out) where
    out[..., k, h, w] = sum_{s, dw} psi[k, h, s, dw] * x[..., lat_idx[h, s],
                                                          (w*stride+dw) % W_in].
    """
    xg = _gather_band(x, lat_idx, affine, psi.shape[1])  # (..., H_out, S, W)
    return fft_correlate(xg, psi, stride)


def mix_basis(z: jax.Array, w: jax.Array, groups: int = 1) -> jax.Array:
    """Learnable channel mix of basis responses (paper eq. 23).

    z: (..., C_in, K, H, W); w: (C_out, C_in // groups, K) ->
    (..., C_out, H, W).
    """
    c_out, cpg, k = w.shape
    if groups == 1:
        return jnp.einsum("...ikhw,oik->...ohw", z, w)
    zg = z.reshape(z.shape[:-4] + (groups, cpg, k) + z.shape[-2:])
    wg = w.reshape(groups, c_out // groups, cpg, k)
    y = jnp.einsum("...gikhw,goik->...gohw", zg, wg)
    return y.reshape(y.shape[:-4] + (c_out,) + y.shape[-2:])


#: gathered-band elements (..., C_in, H_out, S, W_in) above which the
#: reference path contracts in pieces -- per leading index, then per
#: input-channel chunk -- so its FFT intermediates stay a few GB at
#: 721x1440 instead of tens
_REF_BAND_ELEMS = 2**28


def _reference_conv(w: jax.Array, x: jax.Array, psi: jax.Array,
                    lat_idx: jax.Array, stride: int, groups: int,
                    affine: tuple[int, int] | None) -> jax.Array:
    """FFT-path DISCO conv without bias, memory-bounded at full width."""
    band = x.size // x.shape[-2] * psi.shape[1] * psi.shape[2]
    if band <= _REF_BAND_ELEMS:
        z = disco_conv(x, psi, lat_idx, stride, affine)
        return mix_basis(z, w, groups)       # z: (..., C_in, K, H, W)
    if x.ndim > 3:
        xl = x.reshape((-1,) + x.shape[-3:])
        y = jax.lax.map(lambda xi: _reference_conv(
            w, xi, psi, lat_idx, stride, groups, affine), xl)
        return y.reshape(x.shape[:-3] + y.shape[-3:])
    if groups != 1:
        z = disco_conv(x, psi, lat_idx, stride, affine)
        return mix_basis(z, w, groups)
    # groups == 1: sum the mix over input-channel chunks
    c_out, c_in, _ = w.shape
    n = -(-band // _REF_BAND_ELEMS)
    chunk = -(-c_in // n)
    pad = n * chunk - c_in
    xs = jnp.pad(x, ((0, pad), (0, 0), (0, 0))).reshape(
        (n, chunk) + x.shape[-2:])
    ws = jnp.pad(w, ((0, 0), (0, pad), (0, 0))).reshape(
        c_out, n, chunk, -1).transpose(1, 0, 2, 3)

    def body(acc, xw):
        z = disco_conv(xw[0], psi, lat_idx, stride, affine)
        return acc + mix_basis(z, xw[1]), None

    y0 = jnp.zeros((c_out, psi.shape[1], x.shape[-1] // stride),
                   jnp.result_type(x, w, jnp.float32))
    return jax.lax.scan(body, y0, (xs, ws))[0]


def init_disco_conv(key: jax.Array, c_out: int, c_in: int, n_basis: int,
                    groups: int = 1, bias: bool = True, gain: float = 1.0,
                    dtype=jnp.float32) -> dict:
    """Learnable weights merging basis responses and channels (paper eq. 23).

    weight: (C_out, C_in // groups, K), init N(0, gain / fan_in) with
    fan_in = (C_in/groups)*K (He-style variance preservation, paper C.6).
    Use gain=2.0 when the conv feeds a GELU/ReLU, gain=1.0 for linear
    encoder/decoder convs -- critical for rollout stability in the
    normalization-free FCN3 design.
    """
    if c_in % groups or c_out % groups:
        raise ValueError("channels must divide groups")
    fan_in = (c_in // groups) * n_basis
    wkey, _ = jax.random.split(key)
    params = {
        "weight": jax.random.normal(wkey, (c_out, c_in // groups, n_basis),
                                    dtype) * np.sqrt(gain / fan_in),
    }
    if bias:
        params["bias"] = jnp.zeros((c_out,), dtype)
    return params


def apply_disco_conv(params: dict, x: jax.Array, buffers: dict,
                     stride: int, groups: int = 1,
                     affine: tuple[int, int] | None = None) -> jax.Array:
    """x: (..., C_in, H_in, W_in) -> (..., C_out, H_out, W_out)."""
    y = _reference_conv(params["weight"], x, buffers["psi"],
                        buffers["lat_idx"], stride, groups, affine)
    if "bias" in params:
        y = y + params["bias"][..., :, None, None]
    return y
