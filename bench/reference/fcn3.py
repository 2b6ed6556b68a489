"""Plain float32 FCN3 (paper Section 3 / Appendix C), written against
this package alone: the benchmark's ``fcn3`` family.

The step is the paper's: grouped DISCO encoders onto the latent Gaussian
grid, ``n_blocks`` processor blocks (one global spectral block, then
local DISCO blocks, in each period of ``global_block_every``), bilinear
upsampling, grouped DISCO decoders one pressure level at a time and the
softclamp on water channels.  Every DISCO contraction is the FFT
correlation over a full ``psi``; every transform is a plain SHT.  Run it
under ``jax.default_matmul_precision("highest")`` on a TPU, or its
matmuls take bf16 passes.

What the harness (``bench/run.py``) takes from a family's reference:
``Config.from_dict``, ``Reference(cfg)`` with ``.model.init`` and
``.rollout``, ``make_weights`` and ``weights_key``.  The benchmark makes
the served model's weights here (``init``, the blocks' layer scale and
the LSUV-style calibration of C.6) and hands them to the service as a
checkpoint, so the reference never takes weights from the program.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import disco as discolib
from . import grids as glib
from . import interp as interplib
from . import noise as noiselib
from . import sht as shtlib
from .data import SyntheticERA5, scores


@dataclasses.dataclass(frozen=True)
class Config:
    """FCN3 hyperparameters; defaults are the paper's Table 2."""

    nlat: int = 721
    nlon: int = 1440
    grid: str = "equiangular"
    latent_nlat: int = 360
    latent_nlon: int = 720
    latent_grid: str = "gauss"
    n_levels: int = 13
    n_atmos: int = 5
    n_surface: int = 7
    n_aux: int = 4
    n_noise: int = 8
    atmos_embed: int = 45
    surface_embed: int = 56
    cond_embed: int = 36
    n_blocks: int = 10
    global_block_every: int = 5
    mlp_hidden: int = 1282
    encoder_cutoff: float = 3.0
    latent_cutoff: float = 3.0
    filter_ell_max: int = 2
    filter_m_max: int = 2
    layer_scale_init: float = 1e-3

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @property
    def n_state(self) -> int:
        return self.n_levels * self.n_atmos + self.n_surface

    @property
    def n_cond_in(self) -> int:
        return self.n_aux + self.n_noise

    @property
    def c_latent(self) -> int:
        return self.n_levels * self.atmos_embed + self.surface_embed

    def water_channel_indices(self) -> np.ndarray:
        """[13*z, 13*t, 13*u, 13*v, 13*q, surface...]: every q, and tcwv."""
        q = np.arange(4 * self.n_levels, 5 * self.n_levels)
        return np.concatenate([q, [self.n_levels * self.n_atmos + 6]])

    def block_kinds(self) -> list[str]:
        return ["global" if i % self.global_block_every == 0 else "local"
                for i in range(self.n_blocks)]


# ---------------------------------------------------------------------------
# Processor blocks
# ---------------------------------------------------------------------------

def _init_mlp(key, c_in, c_hidden, c_out):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (c_hidden, c_in)) * np.sqrt(2.0 / c_in),
            "b1": jnp.zeros((c_hidden,)),
            "w2": jax.random.normal(k2, (c_out, c_hidden))
            * np.sqrt(2.0 / c_hidden),
            "b2": jnp.zeros((c_out,))}


def _apply_mlp(p, x):
    h = jnp.einsum("oc,...chw->...ohw", p["w1"], x)
    h = jax.nn.gelu(h + p["b1"][:, None, None])
    return jnp.einsum("oc,...chw->...ohw", p["w2"], h) + p["b2"][:, None, None]


def _init_spectral(key, c_out, c_in, lmax):
    scale = np.sqrt(1.0 / c_in)
    kr, ki = jax.random.split(key)
    return {"w_re": scale * jax.random.normal(kr, (c_out, c_in, lmax)),
            "w_im": scale * jax.random.normal(ki, (c_out, c_in, lmax))}


def _apply_spectral(p, x, tables, nlon):
    """Forward SHT, complex per-degree channel mix, inverse SHT."""
    c = shtlib.sht_forward(x, tables["wpct"])            # (..., C, L, M)
    re, im = jnp.real(c), jnp.imag(c)
    yr = (jnp.einsum("oil,...ilm->...olm", p["w_re"], re)
          - jnp.einsum("oil,...ilm->...olm", p["w_im"], im))
    yi = (jnp.einsum("oil,...ilm->...olm", p["w_re"], im)
          + jnp.einsum("oil,...ilm->...olm", p["w_im"], re))
    return shtlib.sht_inverse(jax.lax.complex(yr, yi), tables["pct"], nlon)


def softclamp(u):
    """Paper eq. (29)."""
    return jnp.where(u <= 0.0, 0.0, jnp.where(u <= 0.5, u * u, u - 0.25))


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

class FCN3:
    """``init`` -> params; ``apply(params, buffers, state, cond)`` -> next
    state, for one member: state (C, H, W), cond (n_aux + n_noise, H, W)."""

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.grid_in = glib.make_grid(cfg.nlat, cfg.nlon, cfg.grid)
        self.grid_latent = glib.make_grid(cfg.latent_nlat, cfg.latent_nlon,
                                          cfg.latent_grid)
        plan = functools.partial(discolib.make_disco_plan,
                                 ell_max=cfg.filter_ell_max,
                                 m_max=cfg.filter_m_max)
        self.enc_plan = plan(self.grid_in, self.grid_latent,
                             cutoff_factor=cfg.encoder_cutoff)
        self.latent_plan = plan(self.grid_latent, self.grid_latent,
                                cutoff_factor=cfg.latent_cutoff)
        self.dec_plan = plan(self.grid_in, self.grid_in,
                             cutoff_factor=cfg.encoder_cutoff)
        self.latent_sht = shtlib.SHT.create(self.grid_latent)
        self.in_sht = shtlib.SHT.create(self.grid_in)
        self.upsample = interplib.BilinearResample.create(self.grid_latent,
                                                          self.grid_in)
        self.noise = noiselib.SphericalDiffusion(sht=self.in_sht)
        self.n_basis = self.enc_plan.n_basis

    def make_buffers(self) -> dict:
        return {"enc": self.enc_plan.buffers(),
                "latent": self.latent_plan.buffers(),
                "dec": self.dec_plan.buffers(),
                "latent_sht": self.latent_sht.buffers()}

    def init(self, key: jax.Array) -> dict:
        """The same pytree, key splits and initial scales as the served
        model's ``FCN3.init`` (a checkpoint of it restores there)."""
        cfg = self.cfg
        keys = jax.random.split(key, 6 + cfg.n_blocks)
        k = self.n_basis
        conv = discolib.init_disco_conv
        params = {
            "enc_atmos": conv(keys[0], cfg.atmos_embed, cfg.n_atmos, k,
                              groups=cfg.n_atmos),
            "enc_surface": conv(keys[1], cfg.surface_embed, cfg.n_surface,
                                k, groups=cfg.n_surface),
            "enc_cond": conv(keys[2], cfg.cond_embed, cfg.n_cond_in, k,
                             groups=cfg.n_cond_in),
            "dec_atmos": conv(keys[3], cfg.n_atmos, cfg.atmos_embed, k,
                              groups=cfg.n_atmos),
            "dec_surface": conv(keys[4], cfg.n_surface, cfg.surface_embed,
                                k, groups=cfg.n_surface),
        }
        blocks = []
        c_in = cfg.c_latent + cfg.cond_embed
        for i, kind in enumerate(cfg.block_kinds()):
            kc, km = jax.random.split(keys[5 + i])
            if kind == "local":
                mix = conv(kc, cfg.c_latent, c_in, k, groups=1, gain=2.0)
            else:
                mix = _init_spectral(kc, cfg.c_latent, c_in, cfg.latent_nlat)
            blocks.append({
                "conv": mix,
                "mlp": _init_mlp(km, cfg.c_latent, cfg.mlp_hidden,
                                 cfg.c_latent),
                "layer_scale": jnp.full((cfg.c_latent,),
                                        cfg.layer_scale_init)})
        params["blocks"] = blocks
        return params

    def calibrate(self, params: dict, buffers: dict, state: jax.Array,
                  cond: jax.Array, rounds: int = 4) -> dict:
        """Rescale encoder and decoder weights so the latent embeddings
        have unit std and one step keeps the state's std, iterated on the
        model's own output (paper C.6: no normalization layer absorbs
        scale errors in FCN3)."""
        cfg = self.cfg
        target = float(jnp.std(state))
        encode = jax.jit(self._encode)
        step = jax.jit(self.apply)
        na = cfg.n_levels * cfg.atmos_embed
        nl = cfg.n_levels * cfg.n_atmos

        def scale(p, s):
            return {**p, "weight": p["weight"] * s}

        x = state
        for _ in range(rounds):
            z, c = encode(params, buffers, x, cond)
            params["enc_atmos"] = scale(params["enc_atmos"],
                                        1.0 / (float(jnp.std(z[:na])) or 1.0))
            params["enc_surface"] = scale(
                params["enc_surface"], 1.0 / (float(jnp.std(z[na:])) or 1.0))
            params["enc_cond"] = scale(params["enc_cond"],
                                       1.0 / (float(jnp.std(c)) or 1.0))
            out = step(params, buffers, x, cond)
            params["dec_atmos"] = scale(
                params["dec_atmos"], target / (float(jnp.std(out[:nl])) or 1.0))
            params["dec_surface"] = scale(
                params["dec_surface"],
                target / (float(jnp.std(out[nl:])) or 1.0))
            x = step(params, buffers, x, cond)
        return params

    def _encode(self, params, buffers, state, cond_in):
        cfg = self.cfg
        nl, na = cfg.n_levels, cfg.n_atmos
        plan = self.enc_plan
        atmos = state[: nl * na].reshape((nl, na) + state.shape[-2:])
        za = discolib.apply_disco_conv(params["enc_atmos"], atmos,
                                       buffers["enc"], plan.stride,
                                       groups=na, affine=plan.affine)
        za = za.reshape((nl * cfg.atmos_embed,) + za.shape[-2:])
        zs = discolib.apply_disco_conv(params["enc_surface"],
                                       state[nl * na:], buffers["enc"],
                                       plan.stride, groups=cfg.n_surface,
                                       affine=plan.affine)
        zc = discolib.apply_disco_conv(params["enc_cond"], cond_in,
                                       buffers["enc"], plan.stride,
                                       groups=cfg.n_cond_in,
                                       affine=plan.affine)
        return jnp.concatenate([za, zs], axis=0), zc

    def _decode(self, params, buffers, latent):
        cfg = self.cfg
        nl = cfg.n_levels
        affine = self.dec_plan.affine
        levels = latent[: nl * cfg.atmos_embed].reshape(
            (nl, cfg.atmos_embed) + latent.shape[-2:])

        def level(lat):
            return discolib.apply_disco_conv(
                params["dec_atmos"], self.upsample(lat), buffers["dec"], 1,
                groups=cfg.n_atmos, affine=affine)

        ua = jax.lax.map(level, levels)
        ua = ua.reshape((nl * cfg.n_atmos,) + ua.shape[-2:])
        us = discolib.apply_disco_conv(
            params["dec_surface"], self.upsample(latent[nl * cfg.atmos_embed:]),
            buffers["dec"], 1, groups=cfg.n_surface, affine=affine)
        return jnp.concatenate([ua, us], axis=0)

    def apply(self, params, buffers, state, cond_in):
        """One 6-hour step of one member (direct prediction, C.7)."""
        cfg = self.cfg
        x, cond = self._encode(params, buffers, state, cond_in)
        for p, kind in zip(params["blocks"], cfg.block_kinds()):
            h = jnp.concatenate([x, cond], axis=0)
            if kind == "local":
                h = discolib.apply_disco_conv(
                    p["conv"], h, buffers["latent"], 1,
                    affine=self.latent_plan.affine)
            else:
                h = _apply_spectral(p["conv"], h, buffers["latent_sht"],
                                    x.shape[-1])
            h = _apply_mlp(p["mlp"], jax.nn.gelu(h))
            x = x + p["layer_scale"][:, None, None] * h
        out = self._decode(params, buffers, x)
        water = np.zeros((cfg.n_state,), bool)
        water[cfg.water_channel_indices()] = True
        return jnp.where(jnp.asarray(water)[:, None, None], softclamp(out),
                         out)


class Reference:
    """Rolls a served request's ensemble: the same initial condition,
    noise stream (``PRNGKey(seed)``, centered antithetic pairs, AR(1)
    keyed by ``fold_in(key, lead)``), forcings (aux at 6 (n + 1) h) and
    truth (``state(sample, n + 1)``) as the service's engine."""

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.model = FCN3(cfg)
        self.ds = SyntheticERA5(cfg, self.model.in_sht)
        self.buffers = self.model.make_buffers()
        self.aw = jnp.asarray(self.model.grid_in.area_weights_2d(),
                              jnp.float32)
        self._step = jax.jit(self.model.apply)
        self._noise = jax.jit(self._noise_fields)
        self._advance = jax.jit(self.model.noise.step)
        self._scores = jax.jit(scores)
        self._state = jax.jit(self.ds.state, static_argnums=1)
        self.pct = self.model.in_sht.table("pct")

    def _noise_fields(self, z_hat, pct):
        return noiselib.center_noise(self.model.noise.to_grid(z_hat, pct),
                                     axis=0)

    def rollout(self, params, sample: int, seed: int, members: int,
                leads: int, scored: bool, spectra: bool) -> list[dict]:
        """Products of leads 0 .. leads-1, each a dict of host arrays."""
        key = jax.random.PRNGKey(seed)
        z_hat = self.model.noise.init_state(key, (members,))
        s = jnp.broadcast_to(self._state(sample, 0, self.pct),
                             (members, self.cfg.n_state)
                             + self.model.grid_in.shape)
        wpct = self.model.in_sht.table("wpct") if spectra else None
        out = []
        for n in range(leads):
            z = self._noise(z_hat, self.pct)
            aux = jnp.asarray(self.ds.aux_fields(6.0 * (n + 1)))
            cond = jnp.concatenate(
                [jnp.broadcast_to(aux, (members,) + aux.shape), z], axis=1)
            s = jnp.stack([self._step(params, self.buffers, s[i], cond[i])
                           for i in range(members)])
            z_hat = self._advance(jax.random.fold_in(key, n), z_hat)
            truth = self._state(sample, n + 1, self.pct) if scored else None
            out.append(jax.device_get(self._scores(s, truth, self.aw, wpct)))
        return out


# ---------------------------------------------------------------------------
# The benchmark's weights
# ---------------------------------------------------------------------------

def weights_key(cfg_file: dict) -> tuple[str, dict]:
    """The checkpoint's directory under the configuration's, and what its
    manifest records of how the weights were made."""
    scale = float(cfg_file["weights_layer_scale"])
    return (f"seed{cfg_file['weights_seed']}_scale{scale}",
            {"weights_seed": cfg_file["weights_seed"],
             "weights_layer_scale": scale})


def make_weights(reference: Reference, cfg_file: dict) -> dict:
    """``init`` in one jitted call from the configuration's
    ``weights_seed``, every block's layer scale set to
    ``weights_layer_scale``, then calibrated (paper C.6) on sample 0 of
    the synthetic data.  The init's layer scale (1e-3) leaves the blocks,
    and with them the noise that spreads the ensemble, all but out of a
    step's output; a trained model's blocks carry its dynamics, and only
    then can a fault in the ensemble show in what is served."""
    scale = float(cfg_file["weights_layer_scale"])
    m = reference.model
    with jax.default_matmul_precision("highest"):
        params = jax.jit(m.init)(jax.random.PRNGKey(cfg_file["weights_seed"]))
        for block in params["blocks"]:
            block["layer_scale"] = jnp.full_like(block["layer_scale"], scale)
        ds = reference.ds
        cond = jnp.concatenate(
            [jnp.asarray(ds.aux_fields(0.0))[None],
             m.noise.to_grid(m.noise.init_state(jax.random.PRNGKey(1),
                                                (1,)))], axis=1)[0]
        return m.calibrate(params, reference.buffers, ds.state(0, 0), cond)
