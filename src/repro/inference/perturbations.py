"""Initial-condition perturbations for ensemble seeding (paper App. E).

The paper's ensembles are seeded two ways on top of the hidden-Markov
noise conditioning:

* **Observation-error sampling** -- Gaussian random fields with the
  climatological angular spectrum, scaled per channel by the
  climatological std, mimicking analysis uncertainty at t0.
* **Bred vectors** (Toth & Kalnay 1993) -- perturbations cycled through
  short model rollouts: perturb, integrate control and perturbed states,
  take the difference, rescale to a target amplitude, repeat.  Cycling
  aligns the perturbation with the fastest-growing directions of the flow
  at t0, so ensemble spread grows at the model's intrinsic error-growth
  rate instead of decaying like unstructured noise.

Both are antithetically centered (paper E.3): members come in +/- pairs
whose mean is exactly the control analysis, halving the sampling noise of
the ensemble mean.  ``ForecastEngine.init_carry`` folds the sampler in so
perturbed members are generated on device inside a compiled program --
perturbation fields never exist on the host.

The module is data-agnostic: the spectral shape (``sigma_l``) and the
per-channel climatological std arrive as arrays.  ``from_dataset`` wires
them from the synthetic-ERA5 surrogate; a real-data deployment would pass
its normalization statistics instead.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.sphere import noise as noiselib
from repro.core.sphere import sht as shtlib
from repro.evaluation import metrics

PERTURB_KINDS = ("none", "obs", "bred")


def validate_member_count(members: int, centered: bool,
                          cfg: "PerturbationConfig") -> list[str]:
    """Up-front member/perturbation compatibility check for CLIs and the
    serving request validator.

    Returns human-readable problem strings (empty = valid) so callers can
    raise a clear ``argparse`` error or HTTP 400 *before* any tracing
    starts, instead of a mid-trace failure or a silently off-center
    ensemble mean.
    """
    problems: list[str] = []
    if members < 1:
        problems.append(f"members must be >= 1, got {members}")
        return problems
    # members == 1 is the degenerate single-trajectory case: there is no
    # pair whose mean could be off-center, so nothing to validate.
    if members % 2 and members > 1:
        if centered:
            problems.append(
                f"antithetic noise centering needs an even member count "
                f"(members come in +/- pairs whose mean is the control); "
                f"got members={members}")
        elif cfg.active and cfg.antithetic:
            problems.append(
                f"antithetic initial-condition perturbations need an even "
                f"member count; got members={members}")
    draws = (members + 1) // 2 if cfg.antithetic else members
    if cfg.ensemble_transform and draws < 2:
        detail = (">= 4 antithetic members" if cfg.antithetic
                  else ">= 2 members")
        problems.append(
            "ensemble_transform needs at least two independent draws to "
            f"orthogonalize ({detail}); got members={members}")
    return problems


@dataclasses.dataclass(frozen=True)
class PerturbationConfig:
    """Initial-condition perturbation hyperparameters.

    kind:        "none" (deterministic replication -- the PR-1 behaviour),
                 "obs" (observation-error sampling) or "bred"
                 (cycled bred vectors).
    amplitude:   target perturbation size per channel, in units of the
                 sampler's ``channel_std`` (area-weighted RMS for bred
                 vectors; pointwise std for obs sampling).  With
                 data-derived stds this is a fraction of the
                 climatological variability; with the default
                 ``channel_std=1`` it is absolute normalized units.
    bred_cycles: breeding cycles (perturb -> integrate -> rescale).
    bred_steps:  model steps per breeding cycle.
    antithetic:  +/- pair centering (E.3); ceil(E/2) independent draws.
    ensemble_transform:
                 orthogonalize the bred draws against each other in the
                 area-weighted inner product after every breeding cycle
                 (ensemble-transform rescaling, Wei et al. 2008) instead
                 of only renormalizing.  Plain breeding collapses all
                 draws onto the single fastest-growing mode; the
                 transform keeps the pairs spanning K distinct growing
                 directions.  Requires kind="bred" and at least two
                 independent draws (>= 4 antithetic members).
    """

    kind: str = "none"
    amplitude: float = 0.05
    bred_cycles: int = 3
    bred_steps: int = 1
    antithetic: bool = True
    ensemble_transform: bool = False

    def __post_init__(self):
        if self.kind not in PERTURB_KINDS:
            raise ValueError(
                f"unknown perturbation kind {self.kind!r}; "
                f"expected one of {PERTURB_KINDS}")
        if self.kind == "bred" and self.bred_cycles < 1:
            raise ValueError("bred perturbations need bred_cycles >= 1")
        if self.ensemble_transform and self.kind != "bred":
            raise ValueError(
                "ensemble_transform orthogonalizes bred-vector pairs; it "
                f"requires kind='bred', got kind={self.kind!r}")

    @property
    def active(self) -> bool:
        return self.kind != "none"


class InitialConditionPerturbation:
    """Samples perturbed ensemble members around one analysis state.

    Args:
      sht:         IO-resolution spherical-harmonic transform (shared with
                   the model's noise process).
      cfg:         PerturbationConfig.
      area_weights: (H, W) quadrature weights for amplitude norms.
      sigma_l:     (L,) per-degree std of the perturbation spectrum;
                   defaults to the band-limited atmospheric power law of
                   the synthetic-ERA5 surrogate.
      channel_std: scalar or (C,) climatological per-channel std; the
                   perturbation amplitude is ``cfg.amplitude`` times this.
    """

    def __init__(self, sht: shtlib.SHT, cfg: PerturbationConfig,
                 area_weights, sigma_l=None, channel_std=1.0):
        self.sht = sht
        self.cfg = cfg
        self.area_weights = jnp.asarray(area_weights, jnp.float32)
        if sigma_l is None:
            sigma_l = noiselib.power_law_sigma_l(sht.lmax)
        self.sigma_l = jnp.asarray(sigma_l, jnp.float32)
        self.channel_std = jnp.asarray(channel_std, jnp.float32)
        self._buffers: dict | None = None

    @property
    def buffers(self) -> dict:
        """Legendre tables, built lazily and shared with every other
        user of the same SHT (the engine's noise buffers carry the same
        inverse table)."""
        if self._buffers is None:
            self._buffers = self.sht.buffers()
        return self._buffers

    @classmethod
    def from_dataset(cls, sht: shtlib.SHT, cfg: PerturbationConfig, ds
                     ) -> "InitialConditionPerturbation":
        """Wire spectrum and climatological std from a SyntheticERA5-like
        dataset (anything exposing ``spectrum_sigma_l`` / ``channel_std`` /
        ``grid``)."""
        return cls(sht, cfg, ds.grid.area_weights_2d(),
                   sigma_l=ds.spectrum_sigma_l, channel_std=ds.channel_std())

    # ------------------------------------------------------------------
    def _n_draws(self, members: int) -> int:
        return (members + 1) // 2 if self.cfg.antithetic else members

    def _expand(self, p: jax.Array, members: int) -> jax.Array:
        if self.cfg.antithetic:
            return noiselib.antithetic_expand(p, members, axis=0)
        return p

    def _channel_scale(self, n_channels: int) -> jax.Array:
        return (self.cfg.amplitude
                * jnp.broadcast_to(self.channel_std, (n_channels,)))

    # ------------------------------------------------------------------
    def obs_vectors(self, key: jax.Array, n: int, n_channels: int,
                    sht_buffers: dict | None = None) -> jax.Array:
        """(n, C, H, W) independent obs-error fields.

        Unit pointwise variance by the sigma_l normalization, scaled per
        channel to ``amplitude * channel_std`` -- a draw from the assumed
        (spectrally correlated, spatially homogeneous) analysis-error
        distribution.  ``sht_buffers`` lets jitted callers pass the
        Legendre tables as traced arguments (shardable, not GB-scale HLO
        constants at full resolution); defaults to the precomputed ones.
        """
        b = sht_buffers if sht_buffers is not None else self.buffers
        c = noiselib.sample_spectral_coeffs(
            key, (n, n_channels), self.sigma_l, self.sht.lmax, self.sht.mmax)
        fields = self.sht.inverse(c, b)
        return fields * self._channel_scale(n_channels)[:, None, None]

    def _rescale(self, p: jax.Array) -> jax.Array:
        """Rescale each channel to the target area-weighted RMS amplitude."""
        rms = jnp.sqrt(metrics._spatial_mean(p * p, self.area_weights))
        target = self._channel_scale(p.shape[-3])
        return p * (target / jnp.maximum(rms, 1e-12))[..., None, None]

    def orthogonalize(self, p: jax.Array) -> jax.Array:
        """Ensemble-transform whitening of the draw axis (Wei et al. 2008).

        ``p`` is (K, C, H, W); the K draws are rotated/rescaled by
        ``(P Pt)^(-1/2)`` -- the symmetric inverse square root of their
        Gram matrix in the area-weighted inner product over (C, H, W) --
        so they come out exactly orthonormal.  The symmetric choice (over
        e.g. Gram-Schmidt) perturbs each draw minimally and keeps the
        transform permutation-equivariant.  The K x K eigendecomposition
        is negligible next to one model step, so the transform is cheap
        inside the compiled breeding scan.
        """
        k = p.shape[0]
        if k < 2:
            return p
        w = self.area_weights / jnp.sum(self.area_weights)
        flat = (p * jnp.sqrt(w)).reshape(k, -1)
        gram = flat @ flat.T
        lam, u = jnp.linalg.eigh(gram)
        inv_sqrt = (u / jnp.sqrt(jnp.maximum(lam, 1e-12))) @ u.T
        return jnp.einsum("ij,j...->i...", inv_sqrt, p)

    def bred_vectors(self, key: jax.Array, state0: jax.Array,
                     step_fn: Callable[[jax.Array], jax.Array], n: int,
                     sht_buffers: dict | None = None) -> jax.Array:
        """(n, C, H, W) bred vectors grown by cycled short rollouts.

        Seeded from obs-error draws rescaled to the target amplitude; each
        cycle integrates the control and the perturbed states ``bred_steps``
        model steps, re-extracts the difference and rescales it per channel
        back to ``amplitude * channel_std`` (area-weighted RMS).  With
        ``cfg.ensemble_transform`` the differences are first orthogonalized
        against each other (``orthogonalize``), so the draws track K
        distinct growing directions instead of all collapsing onto the
        leading one.  The final vectors are applied to the *original*
        analysis state0.
        """
        nc = state0.shape[-3]
        p0 = self._rescale(self.obs_vectors(key, n, nc, sht_buffers))

        def cycle(carry, _):
            ctrl, p = carry
            pert = ctrl + p
            for _ in range(self.cfg.bred_steps):
                ctrl = step_fn(ctrl)
                pert = jax.vmap(step_fn)(pert)
            d = pert - ctrl
            if self.cfg.ensemble_transform:
                d = self.orthogonalize(d)
            return (ctrl, self._rescale(d)), None

        (_, p), _ = jax.lax.scan(cycle, (state0, p0), None,
                                 length=self.cfg.bred_cycles)
        return p

    # ------------------------------------------------------------------
    def members(self, key: jax.Array, state0: jax.Array, members: int,
                step_fn: Callable[[jax.Array], jax.Array] | None = None,
                sht_buffers: dict | None = None) -> jax.Array:
        """(E, C, H, W) perturbed ensemble members around ``state0``.

        Dispatches on ``cfg.kind``; "bred" requires ``step_fn`` (one model
        step of the control dynamics).  With antithetic centering each
        +/- pair's mean is the control analysis.
        """
        if not self.cfg.active:
            return jnp.broadcast_to(state0, (members,) + state0.shape)
        k = self._n_draws(members)
        if self.cfg.kind == "obs":
            p = self.obs_vectors(key, k, state0.shape[-3], sht_buffers)
        else:
            if step_fn is None:
                raise ValueError(
                    "bred perturbations need a step_fn (model dynamics)")
            p = self.bred_vectors(key, state0, step_fn, k, sht_buffers)
        return state0 + self._expand(p, members)
