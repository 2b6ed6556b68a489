"""Reference of a stand-in model family for the harness's tests: a
per-point channel mix on a small grid, and nothing of any real model."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Config:
    channels: int = 3
    nlat: int = 4
    nlon: int = 8

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


class Model:
    def __init__(self, cfg: Config):
        self.cfg = cfg

    def init(self, key: jax.Array) -> dict:
        c = self.cfg.channels
        kw, kb = jax.random.split(key)
        return {"mix": {"w": jax.random.normal(kw, (c, c))},
                "bias": jax.random.normal(kb, (c,))}

    def apply(self, params: dict, x: jax.Array) -> jax.Array:
        return (jnp.einsum("oc,chw->ohw", params["mix"]["w"], x)
                + params["bias"][:, None, None])


class Reference:
    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.model = Model(cfg)

    def rollout(self, params, sample: int, seed: int, members: int,
                leads: int, scored: bool, spectra: bool) -> list[dict]:
        cfg = self.cfg
        x = jax.random.normal(jax.random.PRNGKey(sample),
                              (members, cfg.channels, cfg.nlat, cfg.nlon))
        out = []
        for _ in range(leads):
            x = jax.vmap(self.model.apply, in_axes=(None, 0))(params, x)
            out.append({"spectrum": np.asarray(jnp.mean(x * x,
                                                        axis=(0, 3)))})
        return out


def weights_key(cfg_file: dict) -> tuple[str, dict]:
    return (f"seed{cfg_file['weights_seed']}",
            {"weights_seed": cfg_file["weights_seed"]})


def make_weights(reference: Reference, cfg_file: dict) -> dict:
    return jax.jit(reference.model.init)(
        jax.random.PRNGKey(cfg_file["weights_seed"]))
