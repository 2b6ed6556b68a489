"""Pure-jnp oracle for the banded DISCO contraction + channel mix."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.disco.disco import band_rows


def disco_band_contract_ref(x: jax.Array, psi_band: jax.Array,
                            mix: jax.Array, stride: int = 1,
                            affine: tuple = (1, 0),
                            off0: int = 0) -> jax.Array:
    """y[n,q,h,w] = sum_{k,r} mix[k,q,r] sum_{s,d} psi[k,h,s,d]
    * x[n, r, clip(a*h+s+b), (w*stride + off0 + d) % W]."""
    n, r, h_in, w_in = x.shape
    k, h_out, s, d = psi_band.shape
    w_out = w_in // stride
    rows = band_rows(h_out, s, affine, h_in)
    xg = jnp.take(x.astype(jnp.float32), jnp.asarray(rows.reshape(-1)),
                  axis=2).reshape(n, r, h_out, s, w_in)
    lon = (stride * np.arange(w_out)[None, :] + off0
           + np.arange(d)[:, None]) % w_in                    # (D, W_out)
    win = jnp.take(xg, jnp.asarray(lon.reshape(-1)), axis=-1)
    win = win.reshape(n, r, h_out, s, d, w_out)
    z = jnp.einsum("khsd,nrhsdw->nrkhw", psi_band.astype(jnp.float32), win)
    return jnp.einsum("kqr,nrkhw->nqhw", mix.astype(jnp.float32), z)
