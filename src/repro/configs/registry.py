"""The served model families and their named configs, by config name.

Each family's module (``repro.configs.<family>``) holds ``NAMED_CONFIGS``
(name -> config factory) and ``MODEL`` (the model class built from a
config).  A request's ``config``, the launcher's ``--config`` and the
tuner's ``--config`` resolve here, so they name a config of any family.
"""

from __future__ import annotations

import importlib
import types

FAMILIES = ("fcn3", "sfno")


def _modules() -> list[types.ModuleType]:
    return [importlib.import_module(f"repro.configs.{f}") for f in FAMILIES]


def named_configs() -> dict[str, types.ModuleType]:
    """Every config name, mapped to its family's module; a name used by
    two families is an error."""
    out: dict[str, types.ModuleType] = {}
    for mod in _modules():
        for name in mod.NAMED_CONFIGS:
            if name in out:
                raise RuntimeError(f"config {name!r} is named by both "
                                   f"{out[name].__name__} and "
                                   f"{mod.__name__}")
            out[name] = mod
    return out


def family(name: str) -> types.ModuleType:
    """The family module whose ``NAMED_CONFIGS`` holds ``name``."""
    configs = named_configs()
    if name not in configs:
        raise KeyError(f"unknown config {name!r}; expected one of "
                       f"{sorted(configs)}")
    return configs[name]


def config(name: str):
    """The named config's hyperparameters."""
    return family(name).NAMED_CONFIGS[name]()


def build_model(name: str):
    """The named config's model (its family's ``MODEL``)."""
    return family(name).MODEL(config(name))
