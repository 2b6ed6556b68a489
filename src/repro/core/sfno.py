"""The Spherical Fourier Neural Operator (SFNO; Bonev et al., ICML 2023,
arXiv:2306.03838), FCN3's predecessor, deployed as FourCastNet v2.

One 6-hour step of the 73-channel normalized state s (721x1440
equiangular grid), in the layout of NVIDIA makani's
``sfno_linear_73chq_sc3_layers8_edim384``:

  encoder   h  = W_e2 GELU(W_e1 s + b)           73 -> E -> E
  block k   c  = F_k(h)                          sfno.sht
            y  = I_k(K_k c)                      sfno.spectral_mix, sfno.sht
            r  = I_k(c) in blocks 1 and K, else h
            y  = GELU(InstanceNorm(y) + W_in r)  sfno.mlp
            h  = InstanceNorm(MLP(y)) + r        MLP: E -> 2E -> E
  decoder   s' = W_d2 GELU(W_d1 [h; s] + b)      E + 73 -> E -> 73

(encoder, MLP and decoder pointwise; k = 1 .. K).

F_k is the forward SHT on the 721x1440 grid for k = 1 and on the latent
240x480 Gauss grid (scale factor 3) otherwise; I_k inverts onto the
721x1440 grid for k = K and onto the latent grid otherwise; both keep
degrees l < ``lmax`` and orders m <= l.  K_k is the "linear" spectral
filter, a complex E x E channel mix per degree (Driscoll-Healy
convolution; ``spectral_conv`` mode ``full``).  The residual r of the
first and last blocks, where the grid changes, is the unfiltered
coefficients transformed back.  The SHTs, the mix and the Legendre
kernel dispatch are FCN3's own (``core/sphere``, ``kernels/dispatch``).

Departures from makani, and choices its published config leaves open:
no position embedding and no auxiliary input channels (the model
conditions on the state alone, and carries no noise process: it is
deterministic); instance norms are affine, unweighted over the grid,
eps 1e-6; the inner skip W_in and the MLP's second layer carry no bias;
MLP ratio 2.  ``bench/reference/sfno.py`` writes the same equations.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.sphere import grids as glib
from repro.core.sphere import sht as shtlib
from repro.core.sphere import spectral_conv as speclib
from repro.kernels.config import KernelConfig


@dataclasses.dataclass(frozen=True)
class SFNOConfig:
    """SFNO hyperparameters; defaults are makani's
    ``sfno_linear_73chq_sc3_layers8_edim384``."""

    nlat: int = 721
    nlon: int = 1440
    grid: str = "equiangular"
    latent_nlat: int = 240
    latent_nlon: int = 480
    latent_grid: str = "gauss"
    #: degrees kept by every transform (l < lmax; orders m <= l)
    lmax: int = 240
    n_levels: int = 13
    n_atmos: int = 5          # z, t, u, v, q per level
    n_surface: int = 8        # u10m, v10m, u100m, v100m, t2m, sp, msl, tcwv
    embed_dim: int = 384
    n_blocks: int = 8
    mlp_hidden: int = 768
    norm_eps: float = 1e-6
    dtype: str = "float32"
    kernels: KernelConfig = KernelConfig()

    @property
    def n_state(self) -> int:
        return self.n_levels * self.n_atmos + self.n_surface

    @property
    def c_latent(self) -> int:
        return self.embed_dim

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def water_channel_indices(self) -> np.ndarray:
        """Channel order: [13*z, 13*t, 13*u, 13*v, 13*q, surface...]:
        every q, and tcwv (the last surface field)."""
        q = np.arange(4 * self.n_levels, 5 * self.n_levels)
        return np.concatenate([q, [self.n_state - 1]])

    def legendre_table_bytes(self) -> int:
        """float32 bytes of the model's Legendre tables (two per grid)."""
        m = self.lmax
        return 2 * 4 * m * m * (self.nlat + self.latent_nlat)


def _init_pointwise(key: jax.Array, c_in: int, c_hidden: int, c_out: int,
                    dtype) -> dict:
    """W2 GELU(W1 x + b1): He-initialized first layer (it feeds a GELU),
    variance-preserving second layer, no second bias."""
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (c_hidden, c_in), dtype)
            * np.sqrt(2.0 / c_in),
            "b1": jnp.zeros((c_hidden,), dtype),
            "w2": jax.random.normal(k2, (c_out, c_hidden), dtype)
            * np.sqrt(1.0 / c_hidden)}


# Each operator is one jitted function, called inside its named scope:
# the literals of a function lowered in the engine's scan body would
# otherwise take the scan's location, and their broadcasts no scope.

@jax.jit
def _pointwise(p: dict, x: jax.Array) -> jax.Array:
    """Over the channel dim of (..., C, H, W)."""
    h = jnp.einsum("oc,...chw->...ohw", p["w1"], x)
    h = jax.nn.gelu(h + p["b1"][:, None, None])
    return jnp.einsum("oc,...chw->...ohw", p["w2"], h)


def _instance_norm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    """Per channel over the grid, statistics in float32; affine."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(-2, -1), keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=(-2, -1), keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"][:, None, None] + p["bias"][:, None, None]
    return y.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def _block_mlp(p: dict, y: jax.Array, r: jax.Array, eps: float
               ) -> jax.Array:
    """The block after its filter: norm and inner skip, MLP, norm, and
    the residual."""
    y = (_instance_norm(p["norm0"], y, eps)
         + jnp.einsum("oc,...chw->...ohw", p["inner_skip"], r))
    y = _pointwise(p["mlp"], jax.nn.gelu(y))
    return _instance_norm(p["norm1"], y, eps) + r


@functools.partial(jax.jit, static_argnames=("kernels",))
def _analysis(x: jax.Array, wpct: jax.Array, kernels: KernelConfig
              ) -> jax.Array:
    return speclib.transforms({"wpct": wpct}, 0, kernels)[0](x)


@functools.partial(jax.jit, static_argnames=("nlon", "kernels"))
def _synthesis(c: jax.Array, pct: jax.Array, nlon: int,
               kernels: KernelConfig) -> jax.Array:
    return speclib.transforms({"pct": pct}, nlon, kernels)[1](c)


_mix = jax.jit(speclib.spectral_mix)


class SFNO:
    """Functional module: ``init`` -> params, ``make_buffers`` -> the
    Legendre tables, ``apply(params, buffers, state) -> next state``.

    What ``repro.inference.engine.ForecastEngine`` asks of a model:
    ``apply``, ``noise`` (None: no noise carry and no conditioning),
    ``in_sht`` (the input grid's transform, which the spectra product
    uses), ``member_activation_bytes`` and ``default_params``."""

    noise = None
    disco_plans: tuple = ()

    def __init__(self, cfg: SFNOConfig):
        self.cfg = cfg
        self.grid_in = glib.make_grid(cfg.nlat, cfg.nlon, cfg.grid)
        self.grid_latent = glib.make_grid(cfg.latent_nlat, cfg.latent_nlon,
                                          cfg.latent_grid)
        self.in_sht = shtlib.SHT.create(self.grid_in, cfg.lmax, cfg.lmax)
        self.latent_sht = shtlib.SHT.create(self.grid_latent, cfg.lmax,
                                            cfg.lmax)

    # ------------------------------------------------------------------
    def make_buffers(self) -> dict:
        dt = self.cfg.jdtype
        return {name: {k: v.astype(dt) for k, v in sht.buffers().items()}
                for name, sht in (("in", self.in_sht),
                                  ("latent", self.latent_sht))}

    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        dt = cfg.jdtype
        e = cfg.embed_dim
        keys = jax.random.split(key, 2 + cfg.n_blocks)
        blocks = []
        for i in range(cfg.n_blocks):
            kf, ks, km = jax.random.split(keys[2 + i], 3)
            blocks.append({
                "filter": speclib.init_spectral_filter(kf, e, e, cfg.lmax,
                                                       mode="full", dtype=dt),
                "inner_skip": jax.random.normal(ks, (e, e), dt)
                * np.sqrt(1.0 / e),
                "norm0": {"scale": jnp.ones((e,), dt),
                          "bias": jnp.zeros((e,), dt)},
                "mlp": _init_pointwise(km, e, cfg.mlp_hidden, e, dt),
                "norm1": {"scale": jnp.ones((e,), dt),
                          "bias": jnp.zeros((e,), dt)},
            })
        return {"encoder": _init_pointwise(keys[0], cfg.n_state, e, e, dt),
                "blocks": blocks,
                "decoder": _init_pointwise(keys[1], e + cfg.n_state, e,
                                           cfg.n_state, dt)}

    def default_params(self, ds, buffers: dict, state0: jax.Array) -> dict:
        """Params of a service started without a checkpoint: the seeded
        init, uncalibrated (the instance norms set every block's scale)."""
        return jax.jit(self.init)(jax.random.PRNGKey(0))

    def member_activation_bytes(self, itemsize: int) -> int:
        """One member's largest activation: the last block's MLP hidden
        layer (or the decoder's input) on the 721x1440 grid."""
        cfg = self.cfg
        widest = max(cfg.mlp_hidden, cfg.embed_dim + cfg.n_state)
        return widest * cfg.nlat * cfg.nlon * itemsize

    # ------------------------------------------------------------------
    def _block(self, p: dict, h: jax.Array, k: int, buffers: dict
               ) -> jax.Array:
        cfg = self.cfg
        first, last = k == 0, k == cfg.n_blocks - 1
        wpct = buffers["in" if first else "latent"]["wpct"]
        pct = buffers["in" if last else "latent"]["pct"]
        nlon = (self.grid_in if last else self.grid_latent).nlon
        with jax.named_scope(telemetry.SCOPE_SFNO_SHT):
            c = _analysis(h, wpct, cfg.kernels)
        with jax.named_scope(telemetry.SCOPE_SFNO_MIX):
            kc = _mix(p["filter"], c)
        with jax.named_scope(telemetry.SCOPE_SFNO_SHT):
            y = _synthesis(kc, pct, nlon, cfg.kernels).astype(h.dtype)
            r = (_synthesis(c, pct, nlon, cfg.kernels).astype(h.dtype)
                 if first or last else h)
        with jax.named_scope(telemetry.SCOPE_SFNO_MLP):
            return _block_mlp(p, y, r, cfg.norm_eps)

    def apply(self, params: dict, buffers: dict, state: jax.Array,
              cond: jax.Array | None = None) -> jax.Array:
        """One 6-hour step of one member: state (73, H, W) -> next state.
        ``cond`` is not read (the model takes no conditioning).  Encoder,
        transforms, mixes, block MLPs and decoder run under the named
        scopes of ``repro.telemetry.SFNO_SCOPES``."""
        with jax.named_scope(telemetry.SCOPE_SFNO_ENCODER):
            h = _pointwise(params["encoder"], state)
        for k, p in enumerate(params["blocks"]):
            h = self._block(p, h, k, buffers)
        with jax.named_scope(telemetry.SCOPE_SFNO_DECODER):
            return _pointwise(params["decoder"], jnp.concatenate(
                [h, state.astype(h.dtype)], axis=-3))
