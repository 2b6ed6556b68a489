"""Operations and bytes the FCN3 step's algorithm needs, from its shapes.

Counted is the work of the mathematics, not of an implementation: a
DISCO convolution correlates each input channel with the K basis filters
over the taps of the filter's support disk (geodesic distance below the
cutoff) and mixes the K basis responses into the output channels; a
Legendre transform contracts each order m with the degrees l >= m.  So a
kernel that computes a dense band, padding or zero triangles does more
work than is counted here, and reads as a lower share of its roofline.

Data, weights and tables move at the served precision's width:
``value_bytes`` 4 for float32, 2 for bfloat16 (``VALUE_BYTES``).  A
multiply-add is 2 operations.  Nothing here imports the service.

This is the ``fcn3`` family's counts module (``bench/family.py``): the
harness reads ``VALUE_BYTES``, ``member_step``, ``step_calls`` and
``SCOPES``.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from bench.reference import grids as glib

#: bytes a value of each served precision takes
VALUE_BYTES = {"float32": 4, "bfloat16": 2}
#: Morlet basis size for ell_max = m_max = 2 (sin(0, 0) vanishes)
N_BASIS = 7
#: the ``jax.named_scope`` names the program puts on the FCN3 operators
#: (``repro.telemetry.SCOPES``), which ``bench/scopes.py`` attributes
#: device time to
SCOPES = ("fcn3.encoder", "fcn3.local_conv", "fcn3.spectral_conv",
          "fcn3.mlp", "fcn3.decoder")


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n)

    def min_seconds(self, peaks: dict) -> tuple[float, str]:
        """The least time on a chip of these peaks, and which bound sets it."""
        t_f = self.flops / peaks["flops_per_s"]
        t_b = self.bytes / peaks["hbm_bytes_per_s"]
        return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


@functools.lru_cache(maxsize=16)
def disco_support(nlat_in: int, nlon_in: int, kind_in: str, nlat_out: int,
                  nlon_out: int, kind_out: str, cutoff_factor: float
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Per output ring: the taps in the filter's support disk, and the
    support's half-width in input longitudes (0 .. W_in/2).

    The cutoff is ``cutoff_factor * pi / nlat_out``; the disk is the same
    for every output longitude of a ring, so one column is counted.
    """
    gi = glib.make_grid(nlat_in, nlon_in, kind_in)
    go = glib.make_grid(nlat_out, nlon_out, kind_out)
    cutoff = cutoff_factor * np.pi / nlat_out
    j = np.arange(nlon_in)
    off = np.abs(np.where(j <= nlon_in // 2, j, j - nlon_in))
    cos_dphi = np.cos(gi.lons)
    taps = np.zeros(nlat_out, np.int64)
    half = np.zeros(nlat_out, np.int64)
    for h, to in enumerate(go.colat):
        rows = np.nonzero(np.abs(gi.colat - to) < cutoff)[0]
        ti = gi.colat[rows][:, None]
        cosd = (np.cos(to) * np.cos(ti)
                + np.sin(to) * np.sin(ti) * cos_dphi[None, :])
        inside = np.arccos(np.clip(cosd, -1.0, 1.0)) < cutoff
        taps[h] = int(inside.sum())
        half[h] = int(off[inside.any(axis=0)].max()) if inside.any() else -1
    return taps, half


def kernel_rows(half: np.ndarray, nlon_in: int) -> np.ndarray:
    """Output rings whose support stays within a quarter circle each way.
    Rings past that (near the poles) are wrap rings: a full-circle
    correlation is the algorithm there, and the banded kernel leaves them
    to it."""
    cap = (nlon_in // 2 - 1) // 2
    return half <= cap


def disco(c_in: int, c_out: int, groups: int, grid_in: tuple, grid_out: tuple,
          cutoff_factor: float, lead: int = 1, rows: str = "all",
          value_bytes: int = 4) -> Work:
    """One grouped DISCO convolution of ``lead`` stacked inputs.

    ``grid_in``/``grid_out`` are (nlat, nlon, kind).  ``rows`` = "all"
    counts every output ring, "kernel" only the rings the banded kernel
    computes, "wrap" only the others.
    """
    taps, half = disco_support(*grid_in, *grid_out, float(cutoff_factor))
    keep = kernel_rows(half, grid_in[1])
    sel = {"all": np.ones_like(keep), "kernel": keep, "wrap": ~keep}[rows]
    h_out, w_out = grid_out[0], grid_out[1]
    n_taps = float(taps[sel].sum()) * w_out         # taps over all points
    n_out = float(sel.sum()) * w_out                # output points
    flops = lead * (2.0 * N_BASIS * c_in * n_taps
                    + 2.0 * c_out * (c_in // groups) * N_BASIS * n_out)
    frac = float(sel.sum()) / h_out
    data = lead * value_bytes * (c_in * grid_in[0] * grid_in[1] * frac
                           + c_out * n_out)
    weights = value_bytes * (c_out * (c_in // groups) * N_BASIS
                       + N_BASIS * float(taps[sel].sum()))
    return Work(flops, data + weights)


def _pairs(lmax: int, mmax: int) -> int:
    """(l, m) coefficient slots with m <= l."""
    return sum(lmax - m for m in range(mmax))


def legendre(channels: int, nlat: int, lmax: int, mmax: int,
             value_bytes: int = 4) -> Work:
    """One Legendre contraction (forward or inverse) of ``channels``
    complex fields: real and imaginary parts against the real table."""
    pairs = _pairs(lmax, mmax)
    rows = 2 * channels
    flops = 2.0 * rows * nlat * pairs
    data = value_bytes * rows * (nlat * mmax + pairs)
    return Work(flops, data + value_bytes * pairs * nlat)


def rdft(channels: int, nlat: int, nlon: int, value_bytes: int = 4) -> Work:
    """Real FFTs of length ``nlon`` on every ring (5/2 N log2 N each)."""
    flops = 2.5 * nlon * np.log2(nlon) * channels * nlat
    return Work(flops,
                value_bytes * channels * nlat * (nlon + nlon + 2))


def step_calls(m: dict, value_bytes: int = 4
               ) -> dict[str, list[tuple[Work, int]]]:
    """The FCN3 step of one member by kernel family: [(work, calls)].

    ``m`` is the configuration's ``model`` object (Table 2 keys).
    """
    vb = value_bytes
    io = (m["nlat"], m["nlon"], m["grid"])
    lat = (m["latent_nlat"], m["latent_nlon"], m["latent_grid"])
    c_lat = m["n_levels"] * m["atmos_embed"] + m["surface_embed"]
    c_in_blk = c_lat + m["cond_embed"]
    n_global = len(range(0, m["n_blocks"], m["global_block_every"]))
    n_local = m["n_blocks"] - n_global
    enc, dec = m["encoder_cutoff"], m["encoder_cutoff"]
    lmax = m["latent_nlat"]
    mmax = min(lmax, m["latent_nlon"] // 2 + 1)

    def disco_calls(rows):
        return [
            (disco(m["n_atmos"], m["atmos_embed"], m["n_atmos"], io, lat,
                   enc, lead=m["n_levels"], rows=rows, value_bytes=vb), 1),
            (disco(m["n_surface"], m["surface_embed"], m["n_surface"], io,
                   lat, enc, rows=rows, value_bytes=vb), 1),
            (disco(m["n_aux"] + m["n_noise"], m["cond_embed"],
                   m["n_aux"] + m["n_noise"], io, lat, enc, rows=rows,
                   value_bytes=vb), 1),
            (disco(c_in_blk, c_lat, 1, lat, lat, m["latent_cutoff"],
                   rows=rows, value_bytes=vb), n_local),
            (disco(m["atmos_embed"], m["n_atmos"], m["n_atmos"], io, io,
                   dec, rows=rows, value_bytes=vb), m["n_levels"]),
            (disco(m["surface_embed"], m["n_surface"], m["n_surface"], io,
                   io, dec, rows=rows, value_bytes=vb), 1),
        ]

    pairs = _pairs(lmax, mmax)
    hw = m["latent_nlat"] * m["latent_nlon"]
    mlp = Work(2.0 * 2 * c_lat * m["mlp_hidden"] * hw,
               vb * (2 * c_lat * hw + 2 * c_lat * m["mlp_hidden"]))
    spectral_mix = Work(8.0 * c_lat * c_in_blk * pairs,
                        vb * (2 * c_lat * c_in_blk * lmax
                                 + 2 * pairs * (c_lat + c_in_blk)))
    return {
        "disco_kernel": disco_calls("kernel"),
        "disco_wrap": disco_calls("wrap"),
        "legendre": [
            (legendre(c_in_blk, m["latent_nlat"], lmax, mmax, vb), n_global),
            (legendre(c_lat, m["latent_nlat"], lmax, mmax, vb), n_global),
        ],
        "longitude_dft": [
            (rdft(c_in_blk, m["latent_nlat"], m["latent_nlon"], vb),
             n_global),
            (rdft(c_lat, m["latent_nlat"], m["latent_nlon"], vb), n_global),
        ],
        "spectral_mix": [(spectral_mix, n_global)],
        "mlp": [(mlp, m["n_blocks"])],
    }


def total(calls: list[tuple[Work, int]]) -> Work:
    out = Work()
    for w, n in calls:
        out = out + w * n
    return out


def member_step(m: dict, value_bytes: int = 4) -> Work:
    """The whole step of one member: every family above."""
    out = Work()
    for calls in step_calls(m, value_bytes).values():
        out = out + total(calls)
    return out
