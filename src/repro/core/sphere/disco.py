"""Discrete-continuous (DISCO) convolutions on the sphere (paper B.5).

The DISCO convolution, eq. (20), rotates a compactly supported continuous
filter analytically and approximates the S^2 integral with the grid's
quadrature rule:

    (u (x) k)(x_i) ~= sum_j  k(R_i^{-1} x_j) u(x_j) w_j .

For tensor-product grids the filter tensor ``psi[k, h_out, h_in, dw]``
depends only on the output latitude ``h_out``, the input latitude ``h_in``
and the longitude *offset* ``dw`` (paper eq. 55), so the contraction is a
circular correlation along longitude per (h_out, h_in) pair of rings.  The
latitudinal support is a narrow band of ``S`` rings around ``h_out``
(wider longitudinal support near the poles is retained exactly -- psi keeps
the full circle of offsets and is simply zero outside the geodesic cutoff).

Two execution paths produce identical results, selected per
``repro.kernels.config.KernelConfig`` (see docs/kernels.md):

* ``disco_conv`` (this file) -- FFT-based circular correlation (exact,
  XLA-friendly) over the full psi tensor;
* ``repro.kernels.disco`` -- Pallas TPU kernel operating on the densified
  band (the analogue of the paper's custom CUDA contraction kernel).
  ``split_psi_band`` separates psi into the narrow interior band this
  kernel consumes and the few near-pole *wrap rows* whose support circles
  the globe; dispatch recomputes those by the exact FFT correlation, so
  the full (K, H, S, W) psi never needs to be materialized on device.
  ``band_runs`` groups the interior rows into runs that share one
  Toeplitz window, and the kernel is called once per run.

Filter basis: Morlet-like wavelets on the cutoff disk, paper eq. (24):
``k_{l,m}(t', a) = cos^2(pi/2 t') * exp(i pi t' (l sin a + m cos a))``,
realified into cosine/sine pairs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sphere import fourier
from repro.core.sphere import grids as glib
from repro.kernels.config import BLOCK_DEFAULTS, KernelConfig, disco_window


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
    """Precomputed geometry for a DISCO convolution between two grids.

    Attributes:
      psi: (K, H_out, S, W_in) float32 -- quadrature-weighted filter values;
        entry [k, h, s, dw] multiplies u[lat_idx[h, s], (w*stride + dw) % W_in].
      lat_idx: (H_out, S) int32 input latitude rows in the band (clamped;
        invalid rows carry zero psi).
      stride: W_in // W_out longitudinal output stride.
      theta_cutoff: filter radius in radians.
    """

    grid_in: glib.SphereGrid
    grid_out: glib.SphereGrid
    n_basis: int
    theta_cutoff: float
    lat_idx: np.ndarray
    psi: np.ndarray
    stride: int
    # affine band structure: lat_idx[h, s] == clip(a*h + s + b, 0, H_in-1)
    # when it holds (true for all tensor-product grid pairs used here);
    # enables a gather-free strided-slice formulation that GSPMD shards
    # (jnp.take over the latitude axis makes the SPMD partitioner
    # *replicate* the operand -- a ~100 TB/step all-gather at FCN3 scale).
    affine: tuple[int, int] | None = None
    # filter hyperparameters the plan was built with: together with the
    # two grids they form the plan's full cache identity (plan_key), so
    # a serialized plan carries everything needed to re-register itself
    # in a fresh process (repro.serving.bundle warm start).
    ell_max: int = 2
    m_max: int = 2
    cutoff_factor: float = 3.0

    def plan_key(self) -> tuple:
        """The 9-tuple cache identity ``_cached_plan`` is keyed by."""
        return (self.grid_in.nlat, self.grid_in.nlon, self.grid_in.kind,
                self.grid_out.nlat, self.grid_out.nlon, self.grid_out.kind,
                self.ell_max, self.m_max, self.cutoff_factor)

    def buffers(self, dtype=jnp.float32,
                kernels: KernelConfig | None = None) -> dict[str, jax.Array]:
        """Device buffers in the layout the resolved kernel path expects.

        Reference (FFT) dispatch materializes the full ``psi`` tensor;
        pallas dispatch materializes only the banded split (see
        ``split_psi_band``) -- at 721x1440 that is the difference between
        a ~200 MB and a ~10 MB static filter footprint per plan.
        """
        if kernels is not None and kernels.resolve("disco")[0] == "pallas":
            return self.banded_buffers(dtype)
        return {
            "psi": jnp.asarray(self.psi, dtype),
            "lat_idx": jnp.asarray(self.lat_idx),
        }

    def banded_buffers(self, dtype=jnp.float32) -> dict[str, jax.Array]:
        """Banded filter split for the Pallas DISCO kernel.

        ``psi_band`` (K, H, S, D) holds the interior rows' narrow
        longitudinal window (wrap rows zeroed); ``psi_wrap``
        (K, H_wrap, S, W) keeps the full circle for the few near-pole
        rows whose support wraps, which dispatch routes through the
        exact FFT path.  The full (K, H, S, W) psi never reaches the
        device.
        """
        band, wrap_rows, psi_wrap = self._banded_split()
        return {
            "psi_band": jnp.asarray(band, dtype),
            "psi_wrap": jnp.asarray(psi_wrap, dtype),
            "wrap_rows": jnp.asarray(wrap_rows, jnp.int32),
            "lat_idx": jnp.asarray(self.lat_idx),
        }

    def _banded_split(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``split_psi_band(self.psi)``, memoized on the (frozen) plan:
        the split copies full-psi-sized tensors (~200 MB per plan at
        721x1440), and make_buffers / buffer_specs / engine layout
        adaptation must not re-pay that per call."""
        cached = getattr(self, "_split_cache", None)
        if cached is None:
            cached = split_psi_band(self.psi)
            object.__setattr__(self, "_split_cache", cached)
        return cached

    @functools.cached_property
    def band_runs(self) -> tuple[tuple[int, int, int], ...]:
        """The banded kernel's row runs ``(h0, h1, d)`` (``band_runs``
        on the memoized split): dispatch makes one kernel call per run."""
        band, wrap_rows, _ = self._banded_split()
        return band_runs(band, wrap_rows, self.stride)

    @property
    def band_window_share(self) -> float:
        """Toeplitz lanes the run calls issue, over those one call on the
        whole band issued: sum of window x rows over the runs, divided by
        the widest row's window x every row."""
        band, _, _ = self._banded_split()
        w_blk = BLOCK_DEFAULTS["disco"]["w_blk"]
        issued = sum(disco_window(d, self.stride, w_blk) * (h1 - h0)
                     for h0, h1, d in self.band_runs)
        return issued / (disco_window(band.shape[-1], self.stride, w_blk)
                         * band.shape[1])

    def buffer_specs(self, dtype=jnp.float32,
                     kernels: KernelConfig | None = None
                     ) -> dict[str, jax.ShapeDtypeStruct]:
        if kernels is not None and kernels.resolve("disco")[0] == "pallas":
            band, wrap_rows, psi_wrap = self._banded_split()
            return {
                "psi_band": jax.ShapeDtypeStruct(band.shape, dtype),
                "psi_wrap": jax.ShapeDtypeStruct(psi_wrap.shape, dtype),
                "wrap_rows": jax.ShapeDtypeStruct(wrap_rows.shape, jnp.int32),
                "lat_idx": jax.ShapeDtypeStruct(self.lat_idx.shape,
                                                jnp.int32),
            }
        return {
            "psi": jax.ShapeDtypeStruct(self.psi.shape, dtype),
            "lat_idx": jax.ShapeDtypeStruct(self.lat_idx.shape, jnp.int32),
        }


@functools.lru_cache(maxsize=32)
def _cached_plan(nlat_in, nlon_in, kind_in, nlat_out, nlon_out, kind_out,
                 ell_max, m_max, cutoff_factor) -> DiscoPlan:
    gi = glib.make_grid(nlat_in, nlon_in, kind_in)
    go = glib.make_grid(nlat_out, nlon_out, kind_out)
    return _build_plan(gi, go, ell_max, m_max, cutoff_factor)


# Plans installed from a warm-start bundle (see repro.serving.bundle):
# keyed like _cached_plan and consulted before it, so a fresh replica
# skips the psi-tensor construction (and, via the seeded _split_cache,
# the banded split) entirely.  install_plan only ever seeds values that
# _build_plan would reproduce bit-for-bit from the same key.
_PLAN_OVERRIDES: dict[tuple, DiscoPlan] = {}


def export_plan(plan: DiscoPlan) -> dict:
    """Serializable payload for one plan: its cache key plus every
    precomputed tensor, including the memoized banded split (so a warm
    replica never re-pays ``split_psi_band``'s full-psi-sized copies).

    ``install_plan`` is the inverse; the payload is plain scalars +
    numpy arrays (npz/JSON-friendly, no jax types).
    """
    band, wrap_rows, psi_wrap = plan._banded_split()
    return {
        "key": plan.plan_key(),
        "n_basis": plan.n_basis,
        "theta_cutoff": plan.theta_cutoff,
        "stride": plan.stride,
        "affine": plan.affine,
        "psi": plan.psi,
        "lat_idx": plan.lat_idx,
        "psi_band": band,
        "wrap_rows": wrap_rows,
        "psi_wrap": psi_wrap,
    }


def install_plan(payload: dict) -> DiscoPlan:
    """Reconstruct a plan from an ``export_plan`` payload and register it
    so ``make_disco_plan`` returns it for the matching key.

    The grids are rebuilt from the key (grid construction is cheap and
    deterministic); the psi tensor and its banded split come from the
    payload, seeded into the plan's ``_split_cache`` memo.
    """
    (nlat_in, nlon_in, kind_in, nlat_out, nlon_out, kind_out,
     ell_max, m_max, cutoff_factor) = payload["key"]
    gi = glib.make_grid(int(nlat_in), int(nlon_in), str(kind_in))
    go = glib.make_grid(int(nlat_out), int(nlon_out), str(kind_out))
    affine = payload["affine"]
    plan = DiscoPlan(
        grid_in=gi, grid_out=go, n_basis=int(payload["n_basis"]),
        theta_cutoff=float(payload["theta_cutoff"]),
        lat_idx=np.asarray(payload["lat_idx"], np.int32),
        psi=np.asarray(payload["psi"], np.float32),
        stride=int(payload["stride"]),
        affine=tuple(int(a) for a in affine) if affine is not None else None,
        ell_max=int(ell_max), m_max=int(m_max),
        cutoff_factor=float(cutoff_factor),
    )
    object.__setattr__(plan, "_split_cache", (
        np.asarray(payload["psi_band"], np.float32),
        np.asarray(payload["wrap_rows"], np.int32),
        np.asarray(payload["psi_wrap"], np.float32)))
    _PLAN_OVERRIDES[plan.plan_key()] = plan
    return plan


def make_disco_plan(grid_in: glib.SphereGrid, grid_out: glib.SphereGrid,
                    ell_max: int = 2, m_max: int = 2,
                    cutoff_factor: float = 3.0) -> DiscoPlan:
    """Build (and cache) the psi tensor.

    theta_cutoff = cutoff_factor * (pi / nlat_out): the filter radius scales
    with the *output* resolution, mirroring torch-harmonics' convention.
    Plans installed from a warm-start bundle (``install_plan``) are
    returned without any construction work.
    """
    if grid_in.nlon % grid_out.nlon:
        raise ValueError("W_out must divide W_in for strided DISCO")
    key = (grid_in.nlat, grid_in.nlon, grid_in.kind,
           grid_out.nlat, grid_out.nlon, grid_out.kind,
           ell_max, m_max, cutoff_factor)
    hit = _PLAN_OVERRIDES.get(key)
    if hit is not None:
        return hit
    return _cached_plan(*key)


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


def split_psi_band(psi: np.ndarray, d_max: int | None = None
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Split the full psi tensor into an interior band + wrap rows.

    Pure host-side geometry (numpy): for each output row, the nonzero
    longitudinal offsets of the quadrature-weighted filter form a
    contiguous window around offset 0 -- narrow in the interior, wrapping
    (a large fraction of) the whole circle for the few rows near the
    poles where the geodesic cutoff disk contains entire latitude rings.

    A row is a *wrap row* when its support half-width exceeds a quarter
    circle (its window would cover more than half of W -- the regime
    where the FFT correlation is the right algorithm anyway) or, with
    ``d_max``, when it does not fit the capped band.  All other rows
    share one symmetric band of D = 2*max_half_width + 1 taps covering
    offsets ``-(D//2) .. D//2``; the convention is baked into dispatch
    (``off0 = -(D // 2)``) so D is recoverable from the buffer shape.
    Dispatch trims the band to each run's own centred taps
    (``band_runs``).

    Returns ``(psi_band, wrap_rows, psi_wrap)``:
      psi_band: (K, H, S, D) with wrap rows zeroed;
      wrap_rows: (H_wrap,) int32 sorted output-row indices;
      psi_wrap: (K, H_wrap, S, W) the wrap rows' full-circle psi.
    The split is lossless by construction: every nonzero entry of psi
    lands in exactly one of the two tensors.
    """
    k, h, s, w = psi.shape
    nz = np.abs(psi).max(axis=(0, 2))                  # (H, W)
    j = np.arange(w)
    off = np.where(j <= w // 2, j, j - w)              # signed offsets
    # per-row support half-width (-1 when the row has no support at all)
    r = np.where(nz > 0, np.abs(off)[None, :], -1).max(axis=1)  # (H,)
    cap = max(0, (w // 2 - 1) // 2)
    if d_max is not None:
        cap = min(cap, max(0, (d_max - 1) // 2))
    wrap = r > cap
    interior = r[~wrap]
    dh = int(interior.max()) if interior.size and interior.max() > 0 else 0
    d = 2 * dh + 1
    wrap_rows = np.where(wrap)[0].astype(np.int32)
    idx = (np.arange(d) - dh) % w
    band = psi[:, :, :, idx].copy()
    band[:, wrap_rows] = 0.0
    psi_wrap = psi[:, wrap_rows].copy()
    return band.astype(np.float32), wrap_rows, psi_wrap.astype(np.float32)


def band_runs(psi_band: np.ndarray, wrap_rows: np.ndarray, stride: int,
              w_blk: int = BLOCK_DEFAULTS["disco"]["w_blk"]
              ) -> tuple[tuple[int, int, int], ...]:
    """Group the band's rows into the runs the kernel is called on.

    Row h's own support is ``D_h = 2 * half_h + 1`` taps, ``half_h`` its
    support half-width (read off ``psi_band``, which holds every nonzero
    entry of the rows that are not wrap rows).  A run ``(h0, h1, d)`` is
    a maximal stretch of consecutive rows, none of them a wrap row, that
    share one Toeplitz window ``disco_window(D_h, stride, w_blk)``; ``d``
    is the odd width of its widest row, so the centred taps
    ``D//2 - d//2 .. D//2 + d//2`` hold every nonzero entry of its rows.
    Wrap rows belong to no run: the rows between runs are exactly
    ``wrap_rows``.  A band whose rows all share one window is one run.
    """
    _, h, _, d_band = psi_band.shape
    taps = np.abs(np.arange(d_band) - d_band // 2)
    nz = np.abs(psi_band).max(axis=(0, 2)) > 0              # (H, D)
    half = np.where(nz, taps[None, :], 0).max(axis=1)       # (H,)
    wrap = np.zeros(h, bool)
    wrap[np.asarray(wrap_rows)] = True
    runs: list[tuple[int, int, int]] = []
    for row in np.flatnonzero(~wrap):
        row, d = int(row), 2 * int(half[row]) + 1
        if runs and runs[-1][1] == row and (
                disco_window(runs[-1][2], stride, w_blk)
                == disco_window(d, stride, w_blk)):
            h0, _, d0 = runs[-1]
            runs[-1] = (h0, row + 1, max(d0, d))
        else:
            runs.append((row, row + 1, d))
    return tuple(runs)


# ---------------------------------------------------------------------------
# Convolution application (FFT path)
# ---------------------------------------------------------------------------

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


def dense_mix(w: jax.Array, groups: int = 1) -> jax.Array:
    """(C_out, C_in // groups, K) grouped weights -> (K, C_out, C_in)
    block-diagonal dense per-basis mixing matrices."""
    c_out, cpg, k = w.shape
    wg = w.reshape(groups, c_out // groups, cpg, k)
    dense = jnp.einsum("goik,gh->kgohi", wg, jnp.eye(groups, dtype=w.dtype))
    return dense.reshape(k, c_out, groups * cpg)


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
                     affine: tuple[int, int] | None = None,
                     kernels: KernelConfig | None = None,
                     runs: tuple | None = None) -> jax.Array:
    """x: (..., C_in, H_in, W_in) -> (..., C_out, H_out, W_out).

    The contraction dispatches on the buffer layout: banded buffers
    (built by ``DiscoPlan.buffers`` under pallas dispatch) route through
    the Pallas band kernel, once per row run of the plan
    (``DiscoPlan.band_runs``, static like ``affine``), which also applies
    the channel mix, with the FFT fallback on wrap rows; full-psi
    buffers take the reference FFT correlation and need no runs.
    ``kernels`` supplies the interpret flag and tile shape for the
    Pallas call.
    """
    if "psi_band" in buffers:
        from repro.kernels import dispatch as kdispatch
        y = kdispatch.disco_conv_mixed(x, params["weight"], buffers, stride,
                                       groups, affine, runs, kernels)
    else:
        y = _reference_conv(params["weight"], x, buffers["psi"],
                            buffers["lat_idx"], stride, groups, affine)
    if "bias" in params:
        y = y + params["bias"][..., :, None, None]
    return y
