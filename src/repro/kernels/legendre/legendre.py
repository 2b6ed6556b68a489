"""Pallas TPU kernel for the SHT Legendre contraction (paper B.3 / Alg. 1).

The Legendre stage of the SHT is, per Fourier order m, a dense GEMM between
the (B x K) Fourier coefficients and the (K x N) Legendre table slab:

    out[b, n, m] = sum_k  x[b, k, m] * table[m, k, n]

(forward SHT: k = latitude H, n = degree L, table = w_h * Pbar;
 inverse SHT: k = degree L,  n = latitude H, table = Pbar).

This is the compute hot spot of every spectral (global) convolution in FCN3
and the TPU analogue of the cuFFT+GEMM pipeline in torch-harmonics.  The
tables come order-major, (M, K, N) -- the layout ``SHT.table`` builds --
and the wrapper lays x out the same way, (M, B, K), so the Fourier order
is a leading batch dimension of the tiles and the two minor block dims of
every operand are MXU-shaped (b_blk, k_blk, n_blk multiples of (8, 128),
Mosaic's tiling rule).  The kernel tiles (M, B, N) over the grid with an
accumulating K loop as the innermost ("arbitrary") grid dimension and
runs one 2-D matmul per order in the m_blk slab.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.config import block_sizes, default_interpret


def _legendre_kernel(x_ref, t_ref, o_ref):
    """One (m, b, n) tile, accumulating over the k grid dimension.

    x_ref: (M_BLK, B_BLK, K_BLK)  input slab
    t_ref: (M_BLK, K_BLK, N_BLK)  Legendre table slab
    o_ref: (M_BLK, B_BLK, N_BLK)  output tile (revisited across k steps)
    """
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    for i in range(o_ref.shape[0]):
        o_ref[i] += jnp.dot(x_ref[i], t_ref[i],
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret", "blocks"))
def legendre_contract(x: jax.Array, table: jax.Array,
                      interpret: bool | None = None,
                      blocks=None) -> jax.Array:
    """out[b, n, m] = sum_k x[b, k, m] * table[m, k, n].

    x: (B, K, M) float32; table: (M, K, N) float32 -> (B, N, M) float32.
    Shapes are zero-padded up to block multiples; zero padding is exact for
    this bilinear contraction for *any* positive block sizes, so a tuned
    ``blocks`` (``BlockConfig`` for op "legendre") changes only the tiling.
    ``interpret=None`` auto-detects from the backend (compiled on TPU/GPU,
    interpreter elsewhere).
    """
    if interpret is None:
        interpret = default_interpret()
    bs = block_sizes("legendre", blocks)
    b_blk, k_blk, n_blk, m_blk = (bs["b_blk"], bs["k_blk"],
                                  bs["n_blk"], bs["m_blk"])
    b, k, m = x.shape
    m2, k2, n = table.shape
    assert k == k2 and m == m2, (x.shape, table.shape)

    pb, pk, pn, pm = (-b % b_blk), (-k % k_blk), (-n % n_blk), (-m % m_blk)
    xp = jnp.pad(x.astype(jnp.float32).transpose(2, 0, 1),
                 ((0, pm), (0, pb), (0, pk)))
    tp = table.astype(jnp.float32)
    if pm or pk or pn:
        tp = jnp.pad(tp, ((0, pm), (0, pk), (0, pn)))
    gb, gk, gn, gm = ((b + pb) // b_blk, (k + pk) // k_blk,
                      (n + pn) // n_blk, (m + pm) // m_blk)

    out = pl.pallas_call(
        _legendre_kernel,
        grid=(gm, gb, gn, gk),
        in_specs=[
            pl.BlockSpec((m_blk, b_blk, k_blk),
                         lambda im, ib, in_, ik: (im, ib, ik)),
            pl.BlockSpec((m_blk, k_blk, n_blk),
                         lambda im, ib, in_, ik: (im, ik, in_)),
        ],
        out_specs=pl.BlockSpec((m_blk, b_blk, n_blk),
                               lambda im, ib, in_, ik: (im, ib, in_)),
        out_shape=jax.ShapeDtypeStruct((m + pm, b + pb, n + pn),
                                       jnp.float32),
        interpret=interpret,
    )(xp, tp)
    return out[:m, :b, :n].transpose(1, 2, 0)
