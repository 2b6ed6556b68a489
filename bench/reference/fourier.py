"""Longitudinal real Fourier transforms: FFT, or DFT-as-GEMM on a TPU.

On a TPU the short longitude transforms run as GEMMs against DFT
matrices (at "highest" precision they are float32-exact); XLA's inverse
real FFT there holds Hermitian-extended buffers of several GB at
721x1440.  Elsewhere ``jnp.fft``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def get_mode() -> str:
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
def _irdft_mats(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Inverse: irfft(c, n)[w] = Re(c) @ a + Im(c) @ b."""
    nf = n // 2 + 1
    f = np.arange(nf)[:, None]
    w = np.arange(n)[None, :]
    ang = 2.0 * np.pi * f * w / n
    mult = np.full((nf, 1), 2.0)
    mult[0] = 1.0
    if n % 2 == 0:
        mult[-1] = 1.0
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
    """Inverse real FFT along the last axis; c must have n//2+1 entries."""
    assert axis in (-1, c.ndim - 1)
    if get_mode() == "fft":
        return jnp.fft.irfft(c, n=n, axis=-1)
    assert c.shape[-1] == n // 2 + 1, (c.shape, n)
    a, b = _irdft_mats(n)
    return (jnp.real(c) @ jnp.asarray(a) + jnp.imag(c) @ jnp.asarray(b))
