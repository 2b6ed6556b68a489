"""A model family: the modules that hold everything model-specific.

A configuration file (``bench/configs/<config>.json``) names its family
(``"family": "<family>"``), and the harness finds the rest by that
name:

* ``bench/reference/<family>.py``: the plain float32 reference and the
  benchmark's weights (``Config.from_dict``, ``Reference(cfg)`` with
  ``.model.init`` and ``.rollout``, ``make_weights(reference, cfg_file)``,
  ``weights_key(cfg_file)``);
* ``bench/counts/<family>.py``: the algorithm's operations and bytes
  (``VALUE_BYTES``, ``member_step``, ``step_calls``) and the program's
  ``jax.named_scope`` names for the family's operators (``SCOPES``);
* ``repro.configs.<family>``: the service's ``NAMED_CONFIGS``.

So a configuration of a new family is new files alone.
"""

from __future__ import annotations

import importlib
import pkgutil
import types


def name(cfg: dict) -> str:
    """The family a configuration file names; it has no default."""
    if "family" not in cfg:
        raise SystemExit(f"configuration {cfg.get('name')!r} names no "
                         f"family (its key \"family\")")
    return cfg["family"]


def reference(cfg: dict) -> types.ModuleType:
    return importlib.import_module(f"bench.reference.{name(cfg)}")


def counts(cfg: dict) -> types.ModuleType:
    return importlib.import_module(f"bench.counts.{name(cfg)}")


def service_configs(cfg: dict) -> dict:
    """The service's named configurations of the family."""
    return importlib.import_module(f"repro.configs.{name(cfg)}").NAMED_CONFIGS


def all_counts() -> list[types.ModuleType]:
    """Every family's counts module under ``bench/counts/``, by name."""
    import bench.counts
    return [importlib.import_module(f"bench.counts.{m.name}")
            for m in pkgutil.iter_modules(bench.counts.__path__)
            if not m.name.startswith("_")]
