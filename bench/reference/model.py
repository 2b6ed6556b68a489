"""Plain float32 FCN3 (paper Section 3 / Appendix C), its synthetic data
and its served products, written against this package alone.

The step is the paper's: grouped DISCO encoders onto the latent Gaussian
grid, ``n_blocks`` processor blocks (one global spectral block, then
local DISCO blocks, in each period of ``global_block_every``), bilinear
upsampling, grouped DISCO decoders one pressure level at a time and the
softclamp on water channels.  Every DISCO contraction is the FFT
correlation over a full ``psi``; every transform is a plain SHT.  Run it
under ``jax.default_matmul_precision("highest")`` on a TPU, or its
matmuls take bf16 passes.

The benchmark also makes the served model's weights here (``init`` plus
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


# ---------------------------------------------------------------------------
# Synthetic ERA5: the initial conditions, forcings and truth
# ---------------------------------------------------------------------------

class SyntheticERA5:
    """Deterministic spectral surrogate of the 72-channel state: a
    band-limited power law (PSD ~ l^-3 beyond l = 4), AR(1) in time with
    rho = 0.95 per 6 h, a zonal climatology offset, softplus on water
    channels; aux = land/sea masks, orography, cosine zenith angle."""

    def __init__(self, cfg: Config, sht: shtlib.SHT):
        self.cfg = cfg
        self.sht = sht
        self.grid = sht.grid
        self.sigma_l = noiselib.power_law_sigma_l(sht.lmax, 3.0, 4)
        g = self.grid
        lat = np.pi / 2 - g.colat[:, None]
        lon = g.lons[None, :]
        conts = (np.sin(2 * lat) * np.cos(3 * lon)
                 + 0.5 * np.sin(5 * lat + 1.3) * np.sin(2 * lon + 0.7))
        land = (conts > 0.15).astype(np.float32)
        oro = np.maximum(conts - 0.15, 0.0).astype(np.float32) * 2.0
        self.static_aux = np.stack([land, 1.0 - land, oro]).astype(np.float32)

    def aux_fields(self, t_hours: float) -> np.ndarray:
        g = self.grid
        day = t_hours / 24.0
        decl = np.deg2rad(23.44) * np.sin(2 * np.pi * (day - 81.0) / 365.25)
        lat = np.pi / 2 - g.colat
        ha = (t_hours % 24.0) / 24.0 * 2 * np.pi + g.lons[None, :] - np.pi
        cz = (np.sin(lat)[:, None] * np.sin(decl)
              + np.cos(lat)[:, None] * np.cos(decl) * np.cos(ha))
        cz = np.maximum(cz, 0.0).astype(np.float32)
        return np.concatenate([self.static_aux, cz[None]], axis=0)

    def _field(self, key, pct):
        lmax, mmax = self.sht.lmax, self.sht.mmax
        kr, ki = jax.random.split(key)
        shape = (self.cfg.n_state, lmax, mmax)
        re = jax.random.normal(kr, shape)
        im = jax.random.normal(ki, shape)
        m = jnp.arange(mmax)
        im = jnp.where(m == 0, 0.0, im) * np.sqrt(0.5)
        re = re * jnp.where(m == 0, 1.0, np.sqrt(0.5))
        mask = jnp.asarray(shtlib.mode_mask(lmax, mmax), jnp.float32)
        c = (jax.lax.complex(re, im) * mask
             * jnp.asarray(self.sigma_l)[:, None])
        return self.sht.inverse(c, pct)

    def state(self, sample: int, t: int = 0, pct: jax.Array | None = None
              ) -> jax.Array:
        """(C, H, W) state of ``sample`` at 6-hour offset ``t``; pass the
        inverse table ``pct`` under ``jit``."""
        cfg = self.cfg
        base = jax.random.fold_in(jax.random.PRNGKey(20200101), sample)
        x = self._field(jax.random.fold_in(base, 0), pct)
        rho = 0.95
        for k in range(1, t + 1):
            x = (rho * x + np.sqrt(1 - rho * rho)
                 * self._field(jax.random.fold_in(base, k), pct))
        colat = jnp.asarray(self.grid.colat, jnp.float32)
        chan = jnp.arange(cfg.n_state, dtype=jnp.float32)
        x = x + (0.5 * jnp.cos(colat)[None, :, None]
                 * jnp.cos(chan * 0.37)[:, None, None])
        water = np.zeros((cfg.n_state,), bool)
        water[cfg.water_channel_indices()] = True
        return jnp.where(jnp.asarray(water)[:, None, None],
                         jax.nn.softplus(x), x)


# ---------------------------------------------------------------------------
# Served products (paper Appendix D, F.7)
# ---------------------------------------------------------------------------

def _spatial_mean(x, aw):
    return jnp.einsum("...hw,hw->...", x, aw) / jnp.sum(aw)


def scores(ens: jax.Array, truth: jax.Array | None, aw: jax.Array,
           wpct: jax.Array | None) -> dict[str, jax.Array]:
    """Per-channel products of one lead: fair CRPS, ensemble-mean RMSE,
    spread, spread-skill ratio (with the sqrt((E+1)/E) correction) and,
    with ``wpct``, the member-mean angular power spectrum (C, L)."""
    out = {}
    e = ens.shape[0]
    if truth is not None:
        err = jnp.mean(jnp.abs(ens - truth[None]), axis=0)
        pair = jnp.mean(jnp.abs(ens[:, None] - ens[None, :]), axis=(0, 1))
        out["crps"] = _spatial_mean(err - 0.5 * e / (e - 1.0) * pair, aw)
        skill = jnp.sqrt(_spatial_mean((ens.mean(0) - truth) ** 2, aw))
        spread = jnp.sqrt(_spatial_mean(jnp.var(ens, axis=0, ddof=1), aw))
        out["ens_rmse"] = skill
        out["spread"] = spread
        out["ssr"] = jnp.sqrt((e + 1.0) / e) * spread / skill
    if wpct is not None:
        out["spectrum"] = jnp.mean(
            shtlib.spectrum(shtlib.sht_forward(ens, wpct)), axis=0)
    return out


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
