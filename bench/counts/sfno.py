"""Operations and bytes the SFNO step's algorithm needs, from its shapes.

Counted is the work of the mathematics, not of an implementation: a
Legendre transform contracts each order m with the degrees l >= m (the
triangle m <= l < lmax), a longitude transform is a real FFT of each
ring (5/2 N log2 N), the spectral filter is a complex channel mix per
coefficient of that triangle, and the pointwise layers are dense channel
contractions at every grid point.  So a kernel that computes padding, a
dense (l, m) square or the longitude DFT as a GEMM does more work than
is counted here, and reads as a lower share of its roofline.

Data, weights and tables move at the served precision's width
(``VALUE_BYTES``).  A multiply-add is 2 operations.  Nothing here
imports the service.

This is the ``sfno`` family's counts module (``bench/family.py``): the
harness reads ``VALUE_BYTES``, ``member_step``, ``step_calls`` and
``SCOPES``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: bytes a value of each served precision takes
VALUE_BYTES = {"float32": 4, "bfloat16": 2}
#: the ``jax.named_scope`` names the program puts on the SFNO operators
#: (``repro.telemetry.SFNO_SCOPES``)
SCOPES = ("sfno.encoder", "sfno.sht", "sfno.spectral_mix", "sfno.mlp",
          "sfno.decoder")


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


def pairs(lmax: int) -> int:
    """(l, m) coefficient slots with m <= l < lmax."""
    return lmax * (lmax + 1) // 2


def legendre(channels: int, nlat: int, lmax: int, vb: int) -> Work:
    """One Legendre contraction (forward or inverse) of ``channels``
    complex fields on ``nlat`` rings: real and imaginary parts against
    the real table."""
    p = pairs(lmax)
    rows = 2 * channels
    return Work(2.0 * rows * nlat * p,
                vb * (rows * (nlat * lmax + p) + p * nlat))


def rdft(channels: int, nlat: int, nlon: int, vb: int) -> Work:
    """Real FFTs of length ``nlon`` on every ring."""
    return Work(2.5 * nlon * np.log2(nlon) * channels * nlat,
                vb * channels * nlat * (nlon + nlon + 2))


def dense(c_in: int, c_out: int, points: int, vb: int) -> Work:
    """A pointwise c_in -> c_out channel contraction at ``points``."""
    return Work(2.0 * c_in * c_out * points,
                vb * ((c_in + c_out) * points + c_in * c_out))


def step_calls(m: dict, value_bytes: int = 4
               ) -> dict[str, list[tuple[Work, int]]]:
    """The SFNO step of one member by kernel family: [(work, calls)].

    ``m`` is the configuration's ``model`` object.  Of the 2K + 2
    transforms of K blocks, the first block's forward transform and the
    last block's two inverse ones (its output and its residual) are on
    the input grid; the rest, with the first block's residual, on the
    latent grid.
    """
    vb = value_bytes
    e, hid, k = m["embed_dim"], m["mlp_hidden"], m["n_blocks"]
    c = m["n_levels"] * m["n_atmos"] + m["n_surface"]
    lmax = m["lmax"]
    io, lat = (m["nlat"], m["nlon"]), (m["latent_nlat"], m["latent_nlon"])
    n_io, n_lat = io[0] * io[1], lat[0] * lat[1]
    p = pairs(lmax)

    def block_mlp(points):      # inner skip, then E -> 2E -> E
        return [(dense(e, e, points, vb), 1), (dense(e, hid, points, vb), 1),
                (dense(hid, e, points, vb), 1)]

    return {
        "legendre": [(legendre(e, io[0], lmax, vb), 3),
                     (legendre(e, lat[0], lmax, vb), 2 * k - 1)],
        "longitude_dft": [(rdft(e, *io, vb), 3),
                          (rdft(e, *lat, vb), 2 * k - 1)],
        "spectral_mix": [(Work(8.0 * e * e * p,
                               vb * (2 * e * e * lmax + 4 * e * p)), k)],
        "encoder": [(dense(c, e, n_io, vb), 1), (dense(e, e, n_io, vb), 1)],
        "mlp": ([(w, n * (k - 1)) for w, n in block_mlp(n_lat)]
                + block_mlp(n_io)),
        "decoder": [(dense(e + c, e, n_io, vb), 1),
                    (dense(e, c, n_io, vb), 1)],
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
