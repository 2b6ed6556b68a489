"""FCN3 spherical neural-operator processor blocks (paper C.5, Fig. 10).

A spherical adaptation of the ConvNeXt block: a (local DISCO or global
spectral) spherical convolution over the concatenated [latent, conditioning]
state, a GELU, a pointwise two-layer MLP, LayerScale (CaiT), and a residual
connection.  LayerNorm is deliberately omitted (paper C.5): absolute
magnitudes carry physical meaning; stability comes from He-style
variance-preserving initialization (paper C.6) plus LayerScale.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.sphere import disco as discolib
from repro.core.sphere import spectral_conv as speclib
from repro.kernels.config import KernelConfig


def init_mlp(key: jax.Array, c_in: int, c_hidden: int, c_out: int,
             dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (c_hidden, c_in), dtype)
        * np.sqrt(2.0 / c_in),
        "b1": jnp.zeros((c_hidden,), dtype),
        "w2": jax.random.normal(k2, (c_out, c_hidden), dtype)
        * np.sqrt(2.0 / c_hidden),
        "b2": jnp.zeros((c_out,), dtype),
    }


def apply_mlp(params: dict, x: jax.Array) -> jax.Array:
    """Pointwise MLP over channel dim of (..., C, H, W)."""
    h = jnp.einsum("oc,...chw->...ohw", params["w1"], x)
    h = jax.nn.gelu(h + params["b1"][:, None, None])
    y = jnp.einsum("oc,...chw->...ohw", params["w2"], h)
    return y + params["b2"][:, None, None]


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """Static description of one processor block."""

    kind: str              # "local" | "global"
    c_latent: int
    c_cond: int
    mlp_hidden: int
    n_basis: int = 7       # local blocks
    lmax: int = 0          # global blocks
    layer_scale_init: float = 1e-3


def init_block(key: jax.Array, spec: BlockSpec, dtype=jnp.float32) -> dict:
    kc, km = jax.random.split(key)
    c_in = spec.c_latent + spec.c_cond
    if spec.kind == "local":
        # gain 2: the conv feeds a GELU (paper C.6 variance preservation).
        conv = discolib.init_disco_conv(kc, spec.c_latent, c_in, spec.n_basis,
                                        groups=1, gain=2.0, dtype=dtype)
    elif spec.kind == "global":
        conv = speclib.init_spectral_filter(kc, spec.c_latent, c_in, spec.lmax,
                                            mode="full", dtype=dtype)
    else:
        raise ValueError(spec.kind)
    return {
        "conv": conv,
        "mlp": init_mlp(km, spec.c_latent, spec.mlp_hidden, spec.c_latent,
                        dtype),
        "layer_scale": jnp.full((spec.c_latent,), spec.layer_scale_init,
                                dtype),
    }


def apply_block(params: dict, spec: BlockSpec, x: jax.Array, cond: jax.Array,
                buffers: dict,
                affine: tuple[int, int] | None = None,
                runs: tuple | None = None,
                kernels: KernelConfig | None = None) -> jax.Array:
    """One processor block.

    x: (..., C_latent, H, W) latent state; cond: (..., C_cond, H, W)
    conditioning (auxiliary + noise embeddings, constant across blocks).
    buffers: latent-grid geometry -- {"psi", "lat_idx"} (or the banded
    pallas layout) for local blocks and {"wpct", "pct"} for global
    blocks; ``affine`` and ``runs`` are the latent plan's static band
    data for local blocks.  ``kernels`` routes the hot contraction
    through the Pallas substrate (``repro.kernels.dispatch``).
    """
    local = spec.kind == "local"
    with jax.named_scope(telemetry.SCOPE_LOCAL_CONV if local
                         else telemetry.SCOPE_SPECTRAL_CONV):
        cond = jnp.broadcast_to(cond, x.shape[:-3] + cond.shape[-3:])
        h = jnp.concatenate([x, cond], axis=-3)
        if local:
            h = discolib.apply_disco_conv(params["conv"], h, buffers,
                                          stride=1, groups=1, affine=affine,
                                          kernels=kernels, runs=runs)
        else:
            h = speclib.apply_spectral_conv(params["conv"], h, buffers,
                                            nlon=x.shape[-1],
                                            kernels=kernels)
    with jax.named_scope(telemetry.SCOPE_MLP):
        h = jax.nn.gelu(h)
        h = apply_mlp(params["mlp"], h)
        return x + params["layer_scale"][:, None, None] * h


def softclamp(u: jax.Array) -> jax.Array:
    """Smooth positive clamp for water channels, paper eq. (29)."""
    return jnp.where(
        u <= 0.0, 0.0,
        jnp.where(u <= 0.5, u * u, u - 0.25),
    )
