"""The FourCastNet 3 model (paper Section 3 / Appendix C).

Macro architecture (Fig. 1):

  u_n (721x1440 equiangular, 72 channels)
    -> [grouped DISCO encoders, no channel mixing]      (C.3)
    -> latent (360x720 Gaussian, 585 atmos + 56 surface = 641 channels)
    -> 10 spherical neural-operator blocks               (C.5)
       (pattern: 1 global spectral : 4 local DISCO, conditioned on the
        36-channel auxiliary+noise embedding)
    -> [bilinear upsample + grouped DISCO decoders]      (C.4)
    -> softclamp on water channels                       (C.8)
    -> u_{n+1}  (direct state prediction -- no residual path, C.7)

Stochasticity: the model is a hidden Markov model conditioned on 8 spherical
diffusion processes (B.7); different noise draws produce different ensemble
members.

Everything below is pure JAX; static geometry (DISCO psi tensors, Legendre
tables, interpolation plans) is carried in a ``buffers`` pytree produced by
``FCN3.make_buffers`` so it can be sharded/donated and replaced by
``ShapeDtypeStruct`` in compile-only dry-runs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import blocks as blk
from repro.core.sphere import disco as discolib
from repro.core.sphere import grids as glib
from repro.core.sphere import interp as interplib
from repro.core.sphere import noise as noiselib
from repro.core.sphere import sht as shtlib
from repro.kernels.config import KernelConfig


@dataclasses.dataclass(frozen=True)
class FCN3Config:
    """FCN3 hyperparameters (Table 2 defaults = the paper's 710M model)."""

    # grids
    nlat: int = 721
    nlon: int = 1440
    grid: str = "equiangular"
    latent_nlat: int = 360
    latent_nlon: int = 720
    latent_grid: str = "gauss"
    # variables
    n_levels: int = 13
    n_atmos: int = 5          # z, t, u, v, q per level
    n_surface: int = 7        # u10m, v10m, u100m, v100m, t2m, msl, tcwv
    n_aux: int = 4            # lsm-land, lsm-sea, orography, cos zenith
    n_noise: int = 8
    # embedding dims (Table 2)
    atmos_embed: int = 45     # per level
    surface_embed: int = 56
    cond_embed: int = 36
    # processor
    n_blocks: int = 10
    global_block_every: int = 5   # blocks 0, 5 are global: 2 global + 8 local
    mlp_hidden: int = 1282
    # filters
    encoder_cutoff: float = 3.0
    latent_cutoff: float = 3.0
    filter_ell_max: int = 2
    filter_m_max: int = 2
    layer_scale_init: float = 1e-3
    # water channels are softclamped (q at every level + tcwv)
    dtype: str = "float32"
    # kernel substrate for the hot contractions (SHT Legendre stage,
    # banded DISCO): "auto" compiles the Pallas kernels on TPU/GPU and
    # keeps the reference XLA paths on CPU.  Decides both the dispatch
    # in ``apply`` and the buffer layout built by ``make_buffers``.
    kernels: KernelConfig = KernelConfig()

    # ------------------------------------------------------------------
    @property
    def n_state(self) -> int:
        return self.n_levels * self.n_atmos + self.n_surface

    @property
    def n_cond_in(self) -> int:
        return self.n_aux + self.n_noise

    @property
    def c_latent(self) -> int:
        return self.n_levels * self.atmos_embed + self.surface_embed

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def water_channel_indices(self) -> np.ndarray:
        """Channel order: [13*z, 13*t, 13*u, 13*v, 13*q, surface...]."""
        q = np.arange(4 * self.n_levels, 5 * self.n_levels)
        tcwv = np.array([self.n_levels * self.n_atmos + 6])
        return np.concatenate([q, tcwv])

    def legendre_table_bytes(self) -> int:
        """float32 bytes of the Legendre tables in the model's buffers
        (the latent grid's two)."""
        lmax = self.latent_nlat
        return 2 * 4 * lmax * lmax * min(lmax, self.latent_nlon // 2 + 1)

    def block_specs(self) -> list[blk.BlockSpec]:
        n_basis = len(discolib.morlet_basis_spec(self.filter_ell_max,
                                                 self.filter_m_max))
        specs = []
        for i in range(self.n_blocks):
            is_global = (i % self.global_block_every) == 0
            specs.append(blk.BlockSpec(
                kind="global" if is_global else "local",
                c_latent=self.c_latent, c_cond=self.cond_embed,
                mlp_hidden=self.mlp_hidden, n_basis=n_basis,
                lmax=self.latent_nlat,
                layer_scale_init=self.layer_scale_init,
            ))
        return specs


def _runs(plan: discolib.DiscoPlan, buffers: dict) -> tuple | None:
    """The plan's band row runs where ``buffers`` hold the banded split
    (the reference layout needs none, and is spared the split)."""
    return plan.band_runs if "psi_band" in buffers else None


class FCN3:
    """Functional module: ``init`` -> params, ``make_buffers`` -> geometry,
    ``apply(params, buffers, state, cond) -> next state``."""

    def __init__(self, cfg: FCN3Config):
        self.cfg = cfg
        self.grid_in = glib.make_grid(cfg.nlat, cfg.nlon, cfg.grid)
        self.grid_latent = glib.make_grid(cfg.latent_nlat, cfg.latent_nlon,
                                          cfg.latent_grid)
        self.enc_plan = discolib.make_disco_plan(
            self.grid_in, self.grid_latent, cfg.filter_ell_max,
            cfg.filter_m_max, cfg.encoder_cutoff)
        self.latent_plan = discolib.make_disco_plan(
            self.grid_latent, self.grid_latent, cfg.filter_ell_max,
            cfg.filter_m_max, cfg.latent_cutoff)
        self.dec_plan = discolib.make_disco_plan(
            self.grid_in, self.grid_in, cfg.filter_ell_max,
            cfg.filter_m_max, cfg.encoder_cutoff)
        self.latent_sht = shtlib.SHT.create(self.grid_latent)
        self.in_sht = shtlib.SHT.create(self.grid_in)  # losses/noise at IO res
        self.upsample = interplib.BilinearResample.create(self.grid_latent,
                                                          self.grid_in)
        self.noise = noiselib.SphericalDiffusion(sht=self.in_sht)
        self.n_basis = self.enc_plan.n_basis

    @property
    def disco_plans(self) -> tuple:
        """The encoder, latent and decoder DISCO plans."""
        return (self.enc_plan, self.latent_plan, self.dec_plan)

    def member_activation_bytes(self, itemsize: int) -> int:
        """One member's latent activation, the size that decides whether
        the engine steps members side by side."""
        cfg = self.cfg
        return cfg.c_latent * cfg.latent_nlat * cfg.latent_nlon * itemsize

    def default_params(self, ds, buffers: dict, state0: jax.Array) -> dict:
        """Params of a service started without a checkpoint: the
        calibrated init on ``state0`` with fixed keys (PRNGKey(0)
        calibration, PRNGKey(1) noise sample), so the same (config,
        state0) always yields the same params."""
        cond0 = jnp.concatenate(
            [jnp.asarray(ds.aux_fields(0.0))[None],
             self.sample_noise(jax.random.PRNGKey(1), (1,))], axis=1)
        return self.init_calibrated(jax.random.PRNGKey(0), state0[None],
                                    cond0, buffers)

    # ------------------------------------------------------------------
    def make_buffers(self) -> dict:
        """Geometry buffers in the layout ``cfg.kernels`` resolves to.

        Under pallas DISCO dispatch the plans emit the banded split
        (``psi_band`` + near-pole ``psi_wrap``) instead of the full
        (K, H, S, W) psi -- the static-memory win that makes the Pallas
        path viable at 721x1440.
        """
        dt = self.cfg.jdtype
        kc = self.cfg.kernels
        return {
            "enc": self.enc_plan.buffers(dt, kc),
            "latent": self.latent_plan.buffers(dt, kc),
            "dec": self.dec_plan.buffers(dt, kc),
            "latent_sht": {k: v.astype(dt) if v.dtype != jnp.int32 else v
                           for k, v in self.latent_sht.buffers().items()},
        }

    def buffer_specs(self) -> dict:
        dt = self.cfg.jdtype
        kc = self.cfg.kernels
        return {
            "enc": self.enc_plan.buffer_specs(dt, kc),
            "latent": self.latent_plan.buffer_specs(dt, kc),
            "dec": self.dec_plan.buffer_specs(dt, kc),
            "latent_sht": self.latent_sht.buffer_specs(),
        }

    def disco_band_report(self) -> dict:
        """Per DISCO plan, the row runs the banded kernel is called on
        and the share of the whole band's Toeplitz lanes they issue
        (``DiscoPlan.band_window_share``)."""
        return {name: {"runs": len(plan.band_runs),
                       "window_share": round(plan.band_window_share, 4)}
                for name, plan in (("enc", self.enc_plan),
                                   ("latent", self.latent_plan),
                                   ("dec", self.dec_plan))}

    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        dt = cfg.jdtype
        keys = jax.random.split(key, 6 + cfg.n_blocks)
        k_ea, k_es, k_ec, k_da, k_ds = keys[:5]
        params: dict = {
            # Encoders (C.3): one DISCO conv each, grouped per variable so no
            # channel mixing occurs; the atmospheric encoder is shared across
            # the 13 pressure levels (applied level-wise).
            "enc_atmos": discolib.init_disco_conv(
                k_ea, cfg.atmos_embed, cfg.n_atmos, self.n_basis,
                groups=cfg.n_atmos, dtype=dt),
            "enc_surface": discolib.init_disco_conv(
                k_es, cfg.surface_embed, cfg.n_surface, self.n_basis,
                groups=cfg.n_surface, dtype=dt),
            "enc_cond": discolib.init_disco_conv(
                k_ec, cfg.cond_embed, cfg.n_cond_in, self.n_basis,
                groups=cfg.n_cond_in, dtype=dt),
            # Decoders (C.4): grouped DISCO conv at native resolution after
            # bilinear upsampling.
            "dec_atmos": discolib.init_disco_conv(
                k_da, cfg.n_atmos, cfg.atmos_embed, self.n_basis,
                groups=cfg.n_atmos, dtype=dt),
            "dec_surface": discolib.init_disco_conv(
                k_ds, cfg.n_surface, cfg.surface_embed, self.n_basis,
                groups=cfg.n_surface, dtype=dt),
        }
        params["blocks"] = [
            blk.init_block(keys[5 + i], spec, dt)
            for i, spec in enumerate(self.cfg.block_specs())
        ]
        return params

    def init_calibrated(self, key: jax.Array, state: jax.Array,
                        cond_in: jax.Array, buffers: dict | None = None,
                        rounds: int = 4) -> dict:
        """Init + LSUV-style variance calibration (paper C.6 / Fig. 11).

        The paper keeps the uncentered variance constant per layer by careful
        initialization (there is no LayerNorm to absorb scale errors).  A
        fixed analytic gain cannot simultaneously be correct for white and
        for spatially smooth inputs under quadrature-weighted DISCO filters,
        so we calibrate empirically: encoder and decoder weights are rescaled
        by scalars so the latent embeddings and the one-step output preserve
        the input's standard deviation.  Because the relevant input
        distribution during a rollout is the model's *own* output, the
        calibration runs a short fixed-point iteration: calibrate, step the
        state forward, recalibrate on that state.  Processor blocks are
        near-identity at init via LayerScale and need no calibration.
        Each round runs the jitted encoder and step (``self.step``), so
        the full-width model never executes op by op.
        """
        cfg = self.cfg
        params = self.init(key)
        bufs = buffers if buffers is not None else self.make_buffers()
        target = float(jnp.std(state))

        def _scale(p: dict, s: float) -> dict:
            q = dict(p)
            q["weight"] = p["weight"] * s
            return q

        na = cfg.n_levels * cfg.atmos_embed
        nl = cfg.n_levels * cfg.n_atmos
        encode = jax.jit(self._encode)
        x = state
        for _ in range(rounds):
            # 1) encoders -> unit-std latent / conditioning embeddings.
            z, c = encode(params, bufs, x, cond_in)
            params["enc_atmos"] = _scale(
                params["enc_atmos"], 1.0 / (float(jnp.std(z[..., :na, :, :])) or 1.0))
            params["enc_surface"] = _scale(
                params["enc_surface"], 1.0 / (float(jnp.std(z[..., na:, :, :])) or 1.0))
            params["enc_cond"] = _scale(
                params["enc_cond"], 1.0 / (float(jnp.std(c)) or 1.0))
            # 2) decoder -> one full step preserves the state's std.
            out = self.step(params, bufs, x, cond_in)
            params["dec_atmos"] = _scale(
                params["dec_atmos"],
                target / (float(jnp.std(out[..., :nl, :, :])) or 1.0))
            params["dec_surface"] = _scale(
                params["dec_surface"],
                target / (float(jnp.std(out[..., nl:, :, :])) or 1.0))
            # 3) advance the calibration state to the model's own output.
            x = self.step(params, bufs, x, cond_in)
        return params

    # ------------------------------------------------------------------
    def _encode(self, params: dict, buffers: dict, state: jax.Array,
                cond_in: jax.Array) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        nl, na = cfg.n_levels, cfg.n_atmos
        atmos = state[..., : nl * na, :, :]
        surface = state[..., nl * na:, :, :]
        b = atmos.shape[:-3]
        hw = atmos.shape[-2:]
        # (..., L, A, H, W): shared encoder applied per level.
        atmos = atmos.reshape(b + (nl, na) + hw)
        enc = buffers["enc"]
        za = self._conv(params["enc_atmos"], atmos, enc, self.enc_plan, na)
        za = za.reshape(b + (nl * cfg.atmos_embed,) + za.shape[-2:])
        zs = self._conv(params["enc_surface"], surface, enc, self.enc_plan,
                        cfg.n_surface)
        zc = self._conv(params["enc_cond"], cond_in, enc, self.enc_plan,
                        cfg.n_cond_in)
        return jnp.concatenate([za, zs], axis=-3), zc

    def _conv(self, params: dict, x: jax.Array, buffers: dict,
              plan: discolib.DiscoPlan, groups: int) -> jax.Array:
        """One DISCO convolution on ``plan``'s geometry; banded buffers
        also take the plan's static row runs."""
        return discolib.apply_disco_conv(
            params, x, buffers, plan.stride, groups=groups,
            affine=plan.affine, kernels=self.cfg.kernels,
            runs=_runs(plan, buffers))

    def _decode(self, params: dict, buffers: dict, latent: jax.Array
                ) -> jax.Array:
        """Bilinear upsample + grouped DISCO decoders, one pressure level
        at a time: the upsampled latent is the largest activation of the
        step (641 channels at 721x1440, 2.7 GB per member in float32),
        so it is never materialized for all levels at once."""
        cfg = self.cfg
        nl = cfg.n_levels
        atmos_lat = latent[..., : nl * cfg.atmos_embed, :, :]
        surf_lat = latent[..., nl * cfg.atmos_embed:, :, :]
        b = atmos_lat.shape[:-3]
        hw = atmos_lat.shape[-2:]
        levels = jnp.moveaxis(
            atmos_lat.reshape(b + (nl, cfg.atmos_embed) + hw), -4, 0)

        def decode_level(lat):
            return self._conv(params["dec_atmos"], self.upsample(lat),
                              buffers["dec"], self.dec_plan, cfg.n_atmos)

        ua = jnp.moveaxis(jax.lax.map(decode_level, levels), 0, -4)
        ua = ua.reshape(b + (nl * cfg.n_atmos,) + ua.shape[-2:])
        us = self._conv(params["dec_surface"], self.upsample(surf_lat),
                        buffers["dec"], self.dec_plan, cfg.n_surface)
        return jnp.concatenate([ua, us], axis=-3)

    @functools.cached_property
    def step(self):
        """``apply`` under ``jax.jit``: one compiled program per input
        shape, shared by every caller of this model instance."""
        return jax.jit(self.apply)

    def apply(self, params: dict, buffers: dict, state: jax.Array,
              cond_in: jax.Array, *, clamp_water: bool = True) -> jax.Array:
        """One 6-hour step.

        state: (..., 72, H, W) normalized prognostic state u_n.
        cond_in: (..., n_aux + n_noise, H, W) auxiliary + noise fields.
        Returns u_{n+1}, same shape as ``state`` (direct prediction, C.7).
        With ``clamp_water=False`` the decoder output is returned before
        the output transformation (C.8) softclamps the water channels.
        Encoders, blocks and decoder run under the named scopes of
        ``repro.telemetry.SCOPES`` (metadata only: the ops are the same).
        """
        cfg = self.cfg
        with jax.named_scope(telemetry.SCOPE_ENCODER):
            x, cond = self._encode(params, buffers, state, cond_in)
        for p, spec in zip(params["blocks"], cfg.block_specs()):
            buf = (buffers["latent"] if spec.kind == "local"
                   else buffers["latent_sht"])
            # remat per block: activation recomputation keeps the rollout
            # training memory linear in depth (the paper trades this against
            # deeper spatial parallelism; we support both levers).
            local = spec.kind == "local"
            affine = self.latent_plan.affine if local else None
            runs = _runs(self.latent_plan, buf) if local else None
            fn = (lambda pp, xx, cc, bb, _spec=spec, _aff=affine, _runs=runs:
                  blk.apply_block(pp, _spec, xx, cc, bb, affine=_aff,
                                  runs=_runs, kernels=cfg.kernels))
            x = jax.checkpoint(fn)(p, x, cond, buf)
        with jax.named_scope(telemetry.SCOPE_DECODER):
            out = self._decode(params, buffers, x)
            if not clamp_water:
                return out
            # Output transformation (C.8): softclamp water channels.
            water = self.cfg.water_channel_indices()
            mask = np.zeros((cfg.n_state,), bool)
            mask[water] = True
            maskj = jnp.asarray(mask)[:, None, None]
            return jnp.where(maskj, blk.softclamp(out), out)

    # ------------------------------------------------------------------
    def sample_noise(self, key: jax.Array, batch_shape: tuple[int, ...],
                     centered: bool = False) -> jax.Array:
        """Sample the 8 conditioning noise fields at IO resolution.

        Returns (*batch_shape, n_noise, H, W). With ``centered`` (paper E.3)
        the leading axis of batch_shape is treated as the ensemble axis and
        odd members get the negated noise of the preceding even member.
        """
        z_hat = self.noise.init_state(key, batch_shape)
        z = self.noise.to_grid(z_hat)
        if centered:
            z = noiselib.center_noise(z, axis=0)
        return z

    def param_count(self, params: dict) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
