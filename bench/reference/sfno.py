"""Plain float32 SFNO (Bonev et al., ICML 2023, arXiv:2306.03838), written
against this package alone: the benchmark's ``sfno`` family.

One 6-hour step of the 73-channel state s on the 721x1440 equiangular
grid, at the widths of makani's ``sfno_linear_73chq_sc3_layers8_edim384``
(E = 384, 8 blocks, latent 240x480 Gauss grid, degrees l < 240):

  h  = W_e2 GELU(W_e1 s + b)                  encoder, 73 -> E -> E
  block k = 1 .. 8:
    c  = F_k(h)         forward SHT: 721x1440 for k = 1, else latent
    y  = I_k(K_k c)     (K_k c)[o,l,m] = sum_i W_k[o,i,l] c[i,l,m], W_k
                        complex E x E x 240; I_k onto 721x1440 for k = 8,
                        else onto the latent grid
    r  = I_k(c) in blocks 1 and 8 (the grid changes), else h
    y  = GELU(InstanceNorm(y) + W_in r)
    h  = InstanceNorm(W_2 GELU(W_1 y + b_1)) + r      E -> 2E -> E
  s' = W_d2 GELU(W_d1 [h; s] + b)             decoder, E + 73 -> E -> 73

Departures, as in the served model (``repro.core.sfno``): no position
embedding, no auxiliary inputs, no noise; instance norms affine and
unweighted over the grid (eps 1e-6); no bias on W_in nor on any second
pointwise layer.  Every transform is a plain FFT + einsum SHT.  Run it
under ``jax.default_matmul_precision("highest")`` on a TPU, or its
matmuls take bf16 passes.

What the harness (``bench/run.py``) takes from a family's reference:
``Config.from_dict``, ``Reference(cfg)`` with ``.model.init`` and
``.rollout``, ``make_weights`` and ``weights_key``; ``bench/control.py``
puts a changed ``model.apply`` in ``Reference._step``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import grids as glib
from . import sht as shtlib
from .data import SyntheticERA5, scores


@dataclasses.dataclass(frozen=True)
class Config:
    """SFNO hyperparameters; defaults are makani's
    ``sfno_linear_73chq_sc3_layers8_edim384``."""

    nlat: int = 721
    nlon: int = 1440
    grid: str = "equiangular"
    latent_nlat: int = 240
    latent_nlon: int = 480
    latent_grid: str = "gauss"
    lmax: int = 240
    n_levels: int = 13
    n_atmos: int = 5
    n_surface: int = 8
    embed_dim: int = 384
    n_blocks: int = 8
    mlp_hidden: int = 768
    norm_eps: float = 1e-6

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @property
    def n_state(self) -> int:
        return self.n_levels * self.n_atmos + self.n_surface

    def water_channel_indices(self) -> np.ndarray:
        """[13*z, 13*t, 13*u, 13*v, 13*q, surface...]: every q, and tcwv
        (the last surface field)."""
        q = np.arange(4 * self.n_levels, 5 * self.n_levels)
        return np.concatenate([q, [self.n_state - 1]])


def _init_pointwise(key, c_in, c_hidden, c_out):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (c_hidden, c_in)) * np.sqrt(2.0 / c_in),
            "b1": jnp.zeros((c_hidden,)),
            "w2": jax.random.normal(k2, (c_out, c_hidden))
            * np.sqrt(1.0 / c_hidden)}


def _pointwise(p, x):
    h = jax.nn.gelu(jnp.einsum("oc,chw->ohw", p["w1"], x)
                    + p["b1"][:, None, None])
    return jnp.einsum("oc,chw->ohw", p["w2"], h)


def _norm(p, x, eps):
    mean = jnp.mean(x, axis=(-2, -1), keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=(-2, -1), keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * p["scale"][:, None, None]
            + p["bias"][:, None, None])


def _mix(p, c):
    """Complex per-degree channel mix of (C, L, M) coefficients."""
    re, im = jnp.real(c), jnp.imag(c)
    yr = (jnp.einsum("oil,ilm->olm", p["w_re"], re)
          - jnp.einsum("oil,ilm->olm", p["w_im"], im))
    yi = (jnp.einsum("oil,ilm->olm", p["w_re"], im)
          + jnp.einsum("oil,ilm->olm", p["w_im"], re))
    return jax.lax.complex(yr, yi)


class SFNO:
    """``init`` -> params; ``apply(params, buffers, state)`` -> next
    state, for one member: state (73, H, W)."""

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.grid_in = glib.make_grid(cfg.nlat, cfg.nlon, cfg.grid)
        self.grid_latent = glib.make_grid(cfg.latent_nlat, cfg.latent_nlon,
                                          cfg.latent_grid)
        self.in_sht = shtlib.SHT(self.grid_in, cfg.lmax, cfg.lmax)
        self.latent_sht = shtlib.SHT(self.grid_latent, cfg.lmax, cfg.lmax)

    def make_buffers(self) -> dict:
        return {"in": self.in_sht.buffers(),
                "latent": self.latent_sht.buffers()}

    def init(self, key: jax.Array) -> dict:
        """The same pytree, key splits and scales as the served model's
        ``SFNO.init`` (a checkpoint of it restores there)."""
        cfg = self.cfg
        e = cfg.embed_dim
        keys = jax.random.split(key, 2 + cfg.n_blocks)
        blocks = []
        for i in range(cfg.n_blocks):
            kf, ks, km = jax.random.split(keys[2 + i], 3)
            kr, ki = jax.random.split(kf)
            scale = np.sqrt(1.0 / e)
            blocks.append({
                "filter": {
                    "w_re": scale * jax.random.normal(kr, (e, e, cfg.lmax)),
                    "w_im": scale * jax.random.normal(ki, (e, e, cfg.lmax))},
                "inner_skip": jax.random.normal(ks, (e, e)) * scale,
                "norm0": {"scale": jnp.ones((e,)), "bias": jnp.zeros((e,))},
                "mlp": _init_pointwise(km, e, cfg.mlp_hidden, e),
                "norm1": {"scale": jnp.ones((e,)), "bias": jnp.zeros((e,))},
            })
        return {"encoder": _init_pointwise(keys[0], cfg.n_state, e, e),
                "blocks": blocks,
                "decoder": _init_pointwise(keys[1], e + cfg.n_state, e,
                                           cfg.n_state)}

    def apply(self, params, buffers, state):
        """One 6-hour step of one member."""
        cfg = self.cfg
        last = cfg.n_blocks - 1
        h = _pointwise(params["encoder"], state)
        for k, p in enumerate(params["blocks"]):
            src = buffers["in" if k == 0 else "latent"]
            dst = buffers["in" if k == last else "latent"]
            nlon = (self.grid_in if k == last else self.grid_latent).nlon
            c = shtlib.sht_forward(h, src["wpct"])
            y = shtlib.sht_inverse(_mix(p["filter"], c), dst["pct"], nlon)
            r = (shtlib.sht_inverse(c, dst["pct"], nlon)
                 if k in (0, last) else h)
            y = (_norm(p["norm0"], y, cfg.norm_eps)
                 + jnp.einsum("oc,chw->ohw", p["inner_skip"], r))
            y = _pointwise(p["mlp"], jax.nn.gelu(y))
            h = _norm(p["norm1"], y, cfg.norm_eps) + r
        return _pointwise(params["decoder"],
                          jnp.concatenate([h, state], axis=0))


class Reference:
    """Rolls a served request's members: the same initial condition
    (``state(sample, 0)`` of the synthetic data) for every member, one
    deterministic step per lead, and the products the service serves,
    the spectrum taken by the model's own input-grid transform (l < 240)
    as the service takes it; ``seed`` draws nothing (no noise)."""

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.model = SFNO(cfg)
        data_sht = shtlib.SHT.create(self.model.grid_in)
        self.ds = SyntheticERA5(cfg, data_sht)
        self.buffers = self.model.make_buffers()
        self.aw = jnp.asarray(self.model.grid_in.area_weights_2d(),
                              jnp.float32)
        self._step = jax.jit(self.model.apply)
        self._scores = jax.jit(scores)
        self._state = jax.jit(self.ds.state, static_argnums=1)
        self.pct = data_sht.table("pct")

    def rollout(self, params, sample: int, seed: int, members: int,
                leads: int, scored: bool, spectra: bool) -> list[dict]:
        """Products of leads 0 .. leads-1, each a dict of host arrays."""
        s = jnp.broadcast_to(self._state(sample, 0, self.pct),
                             (members, self.cfg.n_state)
                             + self.model.grid_in.shape)
        wpct = self.buffers["in"]["wpct"] if spectra else None
        out = []
        for n in range(leads):
            s = jnp.stack([self._step(params, self.buffers, s[i])
                           for i in range(members)])
            truth = self._state(sample, n + 1, self.pct) if scored else None
            out.append(jax.device_get(self._scores(s, truth, self.aw, wpct)))
        return out


# ---------------------------------------------------------------------------
# The benchmark's weights
# ---------------------------------------------------------------------------

def weights_key(cfg_file: dict) -> tuple[str, dict]:
    """The checkpoint's directory under the configuration's, and what its
    manifest records of how the weights were made."""
    rounds = int(cfg_file["weights_calibration_rounds"])
    return (f"seed{cfg_file['weights_seed']}_cal{rounds}",
            {"weights_seed": cfg_file["weights_seed"],
             "weights_calibration_rounds": rounds})


def make_weights(reference: Reference, cfg_file: dict) -> dict:
    """``init`` from the configuration's ``weights_seed``, then the
    decoder's output layer rescaled so that one step keeps the standard
    deviation of sample 0 of the synthetic data, iterated
    ``weights_calibration_rounds`` times on the model's own output.  The
    instance norms set every block's scale, so the decoder's gain on the
    state is what decides whether a long rollout grows or decays; at the
    calibrated gain the state's deviation is a fixed point it returns to,
    and a 240-step rollout stays finite."""
    m = reference.model
    with jax.default_matmul_precision("highest"):
        params = jax.jit(m.init)(jax.random.PRNGKey(cfg_file["weights_seed"]))
        x = reference._state(0, 0, reference.pct)
        target = float(jnp.std(x))
        for _ in range(int(cfg_file["weights_calibration_rounds"])):
            out = reference._step(params, reference.buffers, x)
            params["decoder"]["w2"] = (params["decoder"]["w2"]
                                       * (target / float(jnp.std(out))))
            x = reference._step(params, reference.buffers, x)
        return params
