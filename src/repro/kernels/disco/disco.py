"""Pallas TPU kernel for the banded DISCO convolution (paper G.2.3, eq. 55).

The paper implements the DISCO contraction as a custom CUDA sparse-dense
kernel.  On TPU there is no efficient gather/sparse unit, so we *densify the
band*: away from the poles the filter support spans S latitude rings and a
narrow window of D longitudinal offsets, giving a dense banded tensor
``psi_band[K, H_out, S, D]``.  Near-pole rows whose support wraps the full
circle take the exact FFT path instead (``repro.kernels.dispatch``).

The kernel computes the contraction *and* the learnable per-basis channel
mix (paper eq. 23) in one pass, so the K-times-wider basis response never
reaches HBM:

    y[n, q, h, w] = sum_{k, r} mix[k, q, r] *
                    sum_{s, d} psi_band[k, h, s, d] *
                               x[n, r, clip(a*h + s + b), (w*stride + off0 + d) mod W]

MXU formulation.  For one output row h and one input ring s, the
longitude correlation of an output tile of ``w_blk`` longitudes reads a
window of ``wwin = w_blk + D - 1`` (rounded up to 128) input longitudes
and is a GEMM against a banded Toeplitz matrix ``T_k[w, i] = psi[k, i - w]``.
The kernel builds ``T`` in VMEM from lane rolls of the tap row (one
strided roll, then log2 doublings) and accumulates ``x_window (c_blk, wwin) @ T^T (wwin, K*w_blk)``
into a VMEM basis-response tile ``z[K, c_blk, W_out]`` -- VMEM grows with
c_blk * W, never with S * D * W.  After the last ring it applies the
channel mix ``mix[k] (Q, c_blk) @ z[k]`` into the output row.

Layout.  The wrapper de-interleaves a longitudinal stride P into P phases
(``x[P*u + ph]``), so the kernel only ever reads unit-stride, lane-aligned
windows, and wrap-pads longitude so windows never wrap in-kernel.  The
latitude gather happens in the BlockSpec index map (clamped affine rows),
so the S-fold gathered band never exists in HBM either.

Grid: (N, H_out, R / c_blk, S); the last two axes accumulate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.config import block_sizes, default_interpret, disco_window

SUBLANE = 8


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def tile_geometry(r: int, d: int, w_out: int, stride: int,
                  blocks=None) -> dict:
    """Static tiling of one banded contraction (shared with the
    autotuner's VMEM model): channel tile, output-longitude tile, Toeplitz
    window, padded extents and tile counts."""
    bs = block_sizes("disco", blocks)
    w_blk = bs["w_blk"]
    if w_blk % SUBLANE or (w_blk // SUBLANE) & (w_blk // SUBLANE - 1):
        raise ValueError(f"disco w_blk={w_blk} must be 8 * 2^k")
    # a channel tile is either the whole (sublane-padded) channel axis or
    # a lane multiple -- it is the minor dim of the mix block
    c_blk = (_round_up(r, SUBLANE) if r <= bs["c_blk"] else bs["c_blk"])
    d_ph = -(-d // stride)
    wwin = disco_window(d, stride, w_blk)
    n_wt = -(-w_out // w_blk)
    return {"c_blk": c_blk, "w_blk": w_blk, "d_ph": d_ph, "wwin": wwin,
            "n_wt": n_wt, "w_out_pad": n_wt * w_blk,
            "w_ext": (n_wt - 1) * w_blk + wwin,
            "r_pad": _round_up(r, c_blk)}


def vmem_bytes(g: dict, k: int, q: int, stride: int) -> int:
    """Float32 VMEM one kernel instance holds for ``tile_geometry`` g:
    the double-buffered x ring, taps, mix and output row; the
    basis-response accumulator; the Toeplitz tile and one GEMM result."""
    c = g["c_blk"]
    return 4 * (2 * (stride * c * g["w_ext"] + stride * k * g["wwin"]
                     + k * q * c + q * g["w_out_pad"])
                + k * c * g["w_out_pad"]
                + k * g["w_blk"] * g["wwin"] + c * k * g["w_blk"])


def _toeplitz(taps: jax.Array, rows: int) -> jax.Array:
    """(1, L) taps -> (rows, L) with ``out[w, i] = taps[(i - w) mod L]``.

    One strided lane roll builds 8 rows; each doubling appends the block
    rolled by its own height (``rows`` = 8 * 2^k).
    """
    t = pltpu.roll(jnp.broadcast_to(taps, (SUBLANE, taps.shape[1])), 0, 1,
                   stride=1, stride_axis=0)
    while t.shape[0] < rows:
        t = jnp.concatenate([t, pltpu.roll(t, t.shape[0], 1)], axis=0)
    return t


def _disco_kernel(x_ref, psi_ref, mix_ref, o_ref, z_ref, *, n_wt: int,
                  w_blk: int, wwin: int):
    """One (n, h, channel-tile, ring) grid step.

    x_ref:   (P, c_blk, w_ext)  one input ring, per stride phase
    psi_ref: (P, K, wwin)       this (h, s)'s band taps, zero past D
    mix_ref: (K, Q, c_blk)      per-basis channel mix
    o_ref:   (Q, w_out_pad)     output row (revisited across c and s)
    z_ref:   (K, c_blk, w_out_pad) VMEM basis-response accumulator
    """
    c, s = pl.program_id(2), pl.program_id(3)
    n_phase, k = psi_ref.shape[0], psi_ref.shape[1]

    @pl.when((c == 0) & (s == 0))
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s == 0)
    def _init_z():
        z_ref[...] = jnp.zeros_like(z_ref)

    for ph in range(n_phase):
        taps = psi_ref[ph]
        # T_k^T[w, i] = psi[k, i - w]: row w is the tap row rolled by w
        toeplitz = jnp.concatenate(
            [_toeplitz(taps[kk:kk + 1], w_blk) for kk in range(k)],
            axis=0)                                       # (K*w_blk, wwin)
        for j in range(n_wt):
            window = x_ref[ph, :, j * w_blk:j * w_blk + wwin]
            zt = jax.lax.dot_general(
                window, toeplitz, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (c_blk, K*w_blk)
            for kk in range(k):
                z_ref[kk, :, j * w_blk:(j + 1) * w_blk] += (
                    zt[:, kk * w_blk:(kk + 1) * w_blk])

    @pl.when(s == pl.num_programs(3) - 1)
    def _mix():
        acc = o_ref[...]
        for kk in range(k):
            acc += jnp.dot(mix_ref[kk], z_ref[kk],
                           preferred_element_type=jnp.float32)
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("stride", "affine", "off0",
                                             "interpret", "blocks"))
def disco_band_contract(x: jax.Array, psi_band: jax.Array, mix: jax.Array,
                        stride: int = 1, affine: tuple = (1, 0),
                        off0: int = 0, interpret: bool | None = None,
                        blocks=None) -> jax.Array:
    """Banded DISCO contraction fused with the per-basis channel mix.

    x: (N, R, H_in, W_in) input rings.
    psi_band: (K, H_out, S, D) banded filter values; tap d sits at
      longitudinal offset ``off0 + d``.
    mix: (K, Q, R) channel mix per basis function.
    stride: longitudinal output stride (W_out = W_in // stride).
    affine: (a, b) -- output row h reads input rows
      ``clip(a*h + s + b, 0, H_in - 1)``.
    interpret: None auto-detects from the backend (compiled on TPU/GPU).
    blocks: ``BlockConfig`` for op "disco" (None = defaults).  Channels
      and output longitudes are zero-padded up to the tiles -- exact for
      any legal tile.

    Returns (N, Q, H_out, W_out) float32.
    """
    if interpret is None:
        interpret = default_interpret()
    n, r, h_in, w_in = x.shape
    k, h_out, s, d = psi_band.shape
    k2, q, r2 = mix.shape
    assert (k, r) == (k2, r2), (x.shape, psi_band.shape, mix.shape)
    assert w_in % stride == 0, (w_in, stride)
    w_out = w_in // stride
    g = tile_geometry(r, d, w_out, stride, blocks)
    c_blk, w_ext, r_pad = g["c_blk"], g["w_ext"], g["r_pad"]
    a, b = affine

    # x -> (N, H_in, P, R_pad, w_ext): phase ph, lane u holds
    # x[(P*u + ph + off0) mod W_in]; the lane extension wraps the ring.
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, r_pad - r),
                                         (0, 0), (0, 0)))
    xf = jnp.roll(xf, -off0, axis=-1) if off0 % w_in else xf
    xf = xf.reshape(n, r_pad, h_in, w_out, stride)
    reps = -(-w_ext // w_out)
    xf = jnp.concatenate([xf] * reps, axis=3)[:, :, :, :w_ext]
    xf = xf.transpose(0, 2, 4, 1, 3)

    # psi -> (H_out, S, P, K, wwin): phase ph, tap e = psi[P*e + ph]
    pp = jnp.pad(psi_band.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, 0), (0, g["d_ph"] * stride - d)))
    pp = pp.reshape(k, h_out, s, g["d_ph"], stride).transpose(1, 2, 4, 0, 3)
    pp = jnp.pad(pp, ((0, 0),) * 4 + ((0, g["wwin"] - g["d_ph"]),))

    mp = jnp.pad(mix.astype(jnp.float32), ((0, 0), (0, 0), (0, r_pad - r)))

    def x_map(ni, hi, ci, si):
        return (ni, jnp.clip(a * hi + si + b, 0, h_in - 1), 0, ci, 0)

    vmem = vmem_bytes(g, k, q, stride)
    out = pl.pallas_call(
        functools.partial(_disco_kernel, n_wt=g["n_wt"], w_blk=g["w_blk"],
                          wwin=g["wwin"]),
        grid=(n, h_out, r_pad // c_blk, s),
        in_specs=[
            pl.BlockSpec((None, None, stride, c_blk, w_ext), x_map),
            pl.BlockSpec((None, None, stride, k, g["wwin"]),
                         lambda ni, hi, ci, si: (hi, si, 0, 0, 0)),
            pl.BlockSpec((k, q, c_blk), lambda ni, hi, ci, si: (0, 0, ci)),
        ],
        out_specs=pl.BlockSpec((None, None, q, g["w_out_pad"]),
                               lambda ni, hi, ci, si: (ni, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h_out, q, g["w_out_pad"]),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, c_blk, g["w_out_pad"]),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=int(max(32 * 2**20, 2 * vmem))),
        interpret=interpret,
    )(xf, pp, mp)
    return out[..., :w_out].transpose(0, 2, 1, 3)


def band_rows(h_out: int, s: int, affine: tuple, h_in: int) -> np.ndarray:
    """(H_out, S) input rows of an affine band (the kernel's index map)."""
    a, b = affine
    rows = a * np.arange(h_out)[:, None] + np.arange(s)[None, :] + b
    return np.clip(rows, 0, h_in - 1)
