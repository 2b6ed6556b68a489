"""Longitudinal Fourier transforms: FFT or DFT-as-GEMM.

XLA's SPMD partitioner **replicates the operands of fft ops even when only
batch dimensions are sharded** (verified: an rfft on a
P("data",None,None,None)-sharded tensor compiles to all-gather + local
full-size FFT).  At FCN3 production scale that turns every DISCO/SHT
longitude transform into a ~TB all-gather (~94 TB/step/device total).

On TPU the idiomatic fix is to cast the short longitudinal transforms
(n_lon = 720/1440) as dense GEMMs against precomputed DFT matrices: the MXU
executes them near peak, GSPMD shards the batch dims freely, and the
matrices (~2-8 MB) are shared constants.  The O(W^2) vs O(W log W) flop
increase is paid on the MXU where FCN3 is nowhere near compute-bound
(see EXPERIMENTS.md SPerf iteration 2).

Mode selection: ``REPRO_DFT_MODE`` environment variable or the
``set_mode`` function; unset, the backend decides -- "matmul" on a TPU,
"fft" elsewhere (fastest on CPU).  On a TPU the FFT path also costs
memory: XLA lowers the inverse real FFT of the latent global blocks
through Hermitian-extended complex buffers, ~3 GB of extra HBM per
full-width member (compile rehearsal of the 721x1440 chunk program),
where the GEMMs need only their inputs and outputs.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

_MODE = os.environ.get("REPRO_DFT_MODE")


def set_mode(mode: str) -> None:
    global _MODE
    assert mode in ("fft", "matmul"), mode
    _MODE = mode


def get_mode() -> str:
    if _MODE is not None:
        return _MODE
    return "matmul" if jax.default_backend() == "tpu" else "fft"


@functools.lru_cache(maxsize=16)
def _rdft_mats(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Forward real-DFT matrices: rfft(x)[f] = x @ (re + i*im)."""
    w = np.arange(n)[:, None]
    f = np.arange(n // 2 + 1)[None, :]
    ang = 2.0 * np.pi * w * f / n
    return (np.cos(ang).astype(np.float32),
            (-np.sin(ang)).astype(np.float32))


@functools.lru_cache(maxsize=16)
def _irdft_mats(n: int, nf: int) -> tuple[np.ndarray, np.ndarray]:
    """Inverse: irfft(c, n)[w] = Re(c) @ a + Im(c) @ b for c of nf orders.

    Row f weighs order f twice (its -f twin), except order 0 and the
    Nyquist order f = n/2, which exists only for even n and is the last
    row only when nf = n//2+1; with nf = n//2+1 these are the full
    inverse matrices.
    """
    f = np.arange(nf)[:, None]
    w = np.arange(n)[None, :]
    ang = 2.0 * np.pi * f * w / n
    mult = np.where((f == 0) | (2 * f == n), 1.0, 2.0)
    a = (mult * np.cos(ang) / n).astype(np.float32)
    b = (-mult * np.sin(ang) / n).astype(np.float32)
    return a, b


def rfft(x: jax.Array, axis: int = -1) -> jax.Array:
    """Real FFT along the last axis (axis must be -1)."""
    assert axis in (-1, x.ndim - 1)
    if get_mode() == "fft":
        # lax.fft accepts only f32/f64; under a bf16 compute policy the
        # longitudinal transform is computed in fp32 (its result is
        # complex64 either way).
        if x.dtype not in (jnp.float32, jnp.float64):
            x = x.astype(jnp.float32)
        return jnp.fft.rfft(x, axis=-1)
    re_m, im_m = _rdft_mats(x.shape[-1])
    xr = x.astype(jnp.float32)
    return jax.lax.complex(xr @ jnp.asarray(re_m), xr @ jnp.asarray(im_m))


def irfft(c: jax.Array, n: int, axis: int = -1) -> jax.Array:
    """Inverse real FFT along the last axis.

    c holds orders 0..k-1 for any k <= n//2+1; the orders it leaves out
    are zero.  The DFT matrices take k rows, so a short spectrum costs k
    rows of work, not n//2+1.  The Nyquist order n/2 (even n only) is
    the last of n//2+1 and counts once, as order 0 does; every other
    order counts twice, for its Hermitian twin.
    """
    assert axis in (-1, c.ndim - 1)
    assert 0 < c.shape[-1] <= n // 2 + 1, (c.shape, n)
    if get_mode() == "fft":
        return jnp.fft.irfft(c, n=n, axis=-1)
    a, b = _irdft_mats(n, c.shape[-1])
    return (jnp.real(c) @ jnp.asarray(a) + jnp.imag(c) @ jnp.asarray(b))
