"""Kernel-dispatch substrate: route SHT and DISCO contractions through
the Pallas kernels (paper App. B.5 / C; the 8-60x inference-speedup
lever) or the reference XLA paths, per ``repro.kernels.config.KernelConfig``.

Three guarantees make the substrate safe to put on the production hot
path:

* **Numerical parity.**  Every pallas route computes the same math as
  its reference path (asserted end-to-end in
  ``tests/test_kernel_dispatch.py``); only the contraction engine
  changes (MXU-tiled GEMMs instead of einsum/FFT).
* **Differentiability.**  The Pallas kernels carry ``jax.custom_vjp``
  rules whose backward passes run the reference oracles, so a model
  dispatched through Pallas still trains / calibrates (the kernels
  themselves define no transpose rules).
* **Exact pole handling.**  The banded DISCO route runs the dense band
  kernel on the interior rows, one call per run of rows that share a
  Toeplitz window (``DiscoPlan.band_runs``), and falls back to the
  exact FFT correlation for the few near-pole *wrap rows* whose filter
  support circles the globe (``repro.core.sphere.disco.split_psi_band``);
  the union covers every nonzero psi entry, so the hybrid is lossless.

Layering: this module may import ``repro.core.sphere`` (pure reference
ops) and the Pallas kernel packages; ``repro.core`` only ever imports it
lazily, inside a function, after ``KernelConfig`` resolved a pallas
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sphere import disco as discolib
from repro.core.sphere import fourier
from repro.core.sphere import sht as shtlib
from repro.kernels.config import KernelConfig, default_interpret
from repro.kernels.disco.disco import disco_band_contract
from repro.kernels.disco.ref import disco_band_contract_ref
from repro.kernels.legendre.legendre import legendre_contract
from repro.kernels.legendre.ref import legendre_contract_ref

_DEFAULT = KernelConfig()


# ---------------------------------------------------------------------------
# Differentiable Pallas primitives (reference-oracle backward passes)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _legendre(x: jax.Array, table: jax.Array, interpret: bool,
              blocks=None) -> jax.Array:
    """Pallas Legendre contraction with a reference-math VJP."""
    return legendre_contract(x, table, interpret=interpret, blocks=blocks)


def _legendre_fwd(x, table, interpret, blocks):
    return _legendre(x, table, interpret, blocks), (x, table)


def _legendre_bwd(interpret, blocks, res, g):
    x, table = res
    _, vjp = jax.vjp(legendre_contract_ref, x, table)
    return vjp(g)


_legendre.defvjp(_legendre_fwd, _legendre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _band_contract(x: jax.Array, psi_band: jax.Array, mix: jax.Array,
                   stride: int, affine: tuple, off0: int, interpret: bool,
                   blocks=None) -> jax.Array:
    """Pallas banded DISCO contraction + channel mix, reference-math VJP."""
    return disco_band_contract(x, psi_band, mix, stride=stride,
                               affine=affine, off0=off0,
                               interpret=interpret, blocks=blocks)


def _band_fwd(x, psi_band, mix, stride, affine, off0, interpret, blocks):
    return (_band_contract(x, psi_band, mix, stride, affine, off0,
                           interpret, blocks), (x, psi_band, mix))


def _band_bwd(stride, affine, off0, interpret, blocks, res, g):
    _, vjp = jax.vjp(
        lambda x_, p_, m_: disco_band_contract_ref(
            x_, p_, m_, stride=stride, affine=affine, off0=off0), *res)
    return vjp(g)


_band_contract.defvjp(_band_fwd, _band_bwd)


# ---------------------------------------------------------------------------
# SHT dispatch
# ---------------------------------------------------------------------------

def _flatten_batch(x: jax.Array, keep: int) -> tuple[jax.Array, tuple]:
    batch = x.shape[:-keep]
    return x.reshape((-1,) + x.shape[-keep:]), batch


def sht_forward_pallas(x: jax.Array, wpct: jax.Array,
                       interpret: bool | None = None,
                       blocks=None) -> jax.Array:
    """Forward SHT with the Legendre stage on the Pallas kernel.

    Same contract (and same longitudinal transform, including the
    DFT-as-GEMM ``REPRO_DFT_MODE``) as ``core.sphere.sht.sht_forward``;
    only the (..., H, M) x (M, H, L) Legendre contraction changes
    engine, on the order-major table as ``SHT.table`` builds it.
    ``blocks`` is the "legendre" tile override (None = defaults).
    """
    if interpret is None:
        interpret = default_interpret()
    m, h, l = wpct.shape
    w = x.shape[-1]
    xf = fourier.rfft(x.astype(jnp.float32), axis=-1)[..., :m]
    xf = xf * (2.0 * jnp.pi / w)
    re, batch = _flatten_batch(jnp.real(xf), 2)
    im, _ = _flatten_batch(jnp.imag(xf), 2)
    cre = _legendre(re, wpct, interpret, blocks)
    cim = _legendre(im, wpct, interpret, blocks)
    return jax.lax.complex(cre, cim).reshape(batch + (l, m))


def sht_inverse_pallas(c: jax.Array, pct: jax.Array, nlon: int,
                       interpret: bool | None = None,
                       blocks=None) -> jax.Array:
    """Inverse SHT with the Legendre stage on the Pallas kernel; ``pct``
    is the order-major (M, L, H) table, contracted over degree L."""
    if interpret is None:
        interpret = default_interpret()
    m, l, h = pct.shape
    re, batch = _flatten_batch(jnp.real(c), 2)
    im, _ = _flatten_batch(jnp.imag(c), 2)
    sr = _legendre(re.astype(jnp.float32), pct, interpret, blocks)
    si = _legendre(im.astype(jnp.float32), pct, interpret, blocks)
    return shtlib.synthesize(jax.lax.complex(sr, si).reshape(batch + (h, m)),
                             nlon)


def sht_forward(x: jax.Array, wpct: jax.Array,
                kernels: KernelConfig | None = None) -> jax.Array:
    """KernelConfig-routed forward SHT (drop-in for the reference)."""
    kc = kernels or _DEFAULT
    path, interpret = kc.resolve("sht")
    if path == "pallas":
        return sht_forward_pallas(x, wpct, interpret,
                                  kc.blocks_for("legendre"))
    return shtlib.sht_forward(x, wpct)


def sht_inverse(c: jax.Array, pct: jax.Array, nlon: int,
                kernels: KernelConfig | None = None) -> jax.Array:
    """KernelConfig-routed inverse SHT (drop-in for the reference)."""
    kc = kernels or _DEFAULT
    path, interpret = kc.resolve("sht")
    if path == "pallas":
        return sht_inverse_pallas(c, pct, nlon, interpret,
                                  kc.blocks_for("legendre"))
    return shtlib.sht_inverse(c, pct, nlon)


# ---------------------------------------------------------------------------
# DISCO dispatch
# ---------------------------------------------------------------------------

#: channel rows one band-kernel call should reach: leading dims of the
#: input are folded into its channel axis until it is at least this wide,
#: so the Toeplitz GEMMs stream enough rows through the MXU
_ROW_TARGET = 128


def band_run_rows(h0: int, h1: int, s: int, affine: tuple[int, int],
                  h_in: int) -> tuple[int, int, tuple[int, int]]:
    """The input rows ``[r0, r1)`` that output rows ``h0 .. h1 - 1`` of
    an affine band of ``s`` rings read (``clip(a*h + s + b, 0, h_in-1)``),
    and the affine map of those rows into the slice: the clip to the
    slice's ends reads the same rows as the clip to the grid's."""
    a, b = affine
    r0 = min(max(a * h0 + b, 0), h_in - 1)
    r1 = min(max(a * (h1 - 1) + s - 1 + b, 0), h_in - 1) + 1
    return r0, r1, (a, a * h0 + b - r0)


def disco_conv_mixed(x: jax.Array, weight: jax.Array, buffers: dict,
                     stride: int, groups: int = 1,
                     affine: tuple[int, int] | None = None,
                     runs: tuple | None = None,
                     kernels: KernelConfig | None = None) -> jax.Array:
    """DISCO convolution without bias on the banded buffers: Pallas band
    kernel (contraction fused with the channel mix) + exact FFT wrap rows.

    x: (..., C_in, H_in, W_in); weight: (C_out, C_in // groups, K) ->
    (..., C_out, H_out, W_out), numerically matching
    ``core.sphere.disco.apply_disco_conv`` on the full psi tensor minus
    its bias.  Buffers come from ``DiscoPlan.banded_buffers``; the band
    tap convention is ``off0 = -(D // 2)``.

    ``runs`` are the plan's static row runs ``(h0, h1, d)``
    (``DiscoPlan.band_runs``).  The band kernel is called once per run,
    on the run's rows, its centred ``d`` taps (the others are zero on
    every row of the run) and only the input rows they read, so each run
    issues the Toeplitz window its own support needs rather than the
    widest row's.  The rows between runs are the wrap rows: the FFT
    correlation computes them, and the pieces are put together in row
    order.
    """
    if affine is None or runs is None:
        raise ValueError("the banded DISCO kernel needs an affine plan "
                         "and its row runs")
    kc = kernels or _DEFAULT
    _, interpret = kc.resolve("disco")
    psi_band = buffers["psi_band"].astype(jnp.float32)
    k, h_out, s, d_band = psi_band.shape
    lead, c_in = x.shape[:-3], x.shape[-3]
    h_in, w_in = x.shape[-2:]
    c_out, w_out = weight.shape[0], w_in // stride
    n_wrap = buffers["wrap_rows"].shape[0]
    if n_wrap != h_out - sum(h1 - h0 for h0, h1, _ in runs):
        raise ValueError(f"runs {runs} leave other rows than the plan's "
                         f"{n_wrap} wrap rows")
    # fold trailing leading dims (e.g. the 13 pressure levels sharing one
    # encoder) into the kernel's channel rows, with a block-diagonal mix
    fold, rows = 0, c_in
    while rows < _ROW_TARGET and fold < len(lead):
        fold += 1
        rows *= lead[-fold]
    nfold = rows // c_in
    mix = discolib.dense_mix(weight.astype(jnp.float32), groups)
    if nfold > 1:
        eye = jnp.eye(nfold, dtype=jnp.float32)
        mix = jnp.einsum("kqr,fg->kfqgr", mix, eye).reshape(
            k, nfold * mix.shape[1], rows)
    xb = x.reshape((-1, rows, h_in, w_in))
    yw = None
    if n_wrap:
        # Exact FFT circular correlation on the wrap rows only; their
        # psi keeps the full circle of offsets.
        wrap_rows = buffers["wrap_rows"]
        rows_w = jnp.take(buffers["lat_idx"], wrap_rows, axis=0)
        xw = jnp.take(x, rows_w.reshape(-1), axis=-2)
        xw = xw.reshape(x.shape[:-2] + rows_w.shape + (w_in,))
        zw = discolib.fft_correlate(xw, buffers["psi_wrap"], stride)
        yw = discolib.mix_basis(zw, weight, groups).astype(jnp.float32)
    pieces, done, n_wrapped = [], 0, 0
    # an empty run at h_out closes the list, so the last wrap rows land
    for h0, h1, d in (*runs, (h_out, h_out, 0)):
        if h0 > done:            # wrap rows, in row order
            pieces.append(yw[..., n_wrapped:n_wrapped + h0 - done, :])
            n_wrapped += h0 - done
        if h1 == h0:
            continue
        r0, r1, run_affine = band_run_rows(h0, h1, s, affine, h_in)
        taps = psi_band[:, h0:h1, :, d_band // 2 - d // 2:
                        d_band // 2 + d // 2 + 1]
        y = _band_contract(xb[:, :, r0:r1], taps, mix, stride, run_affine,
                           -(d // 2), interpret, kc.blocks_for("disco"))
        pieces.append(y.reshape(lead + (c_out, h1 - h0, w_out)))
        done = h1
    return jnp.concatenate(pieces, axis=-2)
