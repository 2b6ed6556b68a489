"""SFNO variable table and named model configs.

[weather] The Spherical Fourier Neural Operator, FourCastNet v2.
Source: Bonev et al., "Spherical Fourier Neural Operators: Learning
Stable Dynamics on the Sphere", ICML 2023 (arXiv:2306.03838); widths of
NVIDIA makani's ``sfno_linear_73chq_sc3_layers8_edim384``.
"""

from __future__ import annotations

from repro.configs.fcn3 import ATMOS_VARS, PRESSURE_LEVELS
from repro.core.sfno import SFNO, SFNOConfig

#: the eight surface fields of the 73-channel set ("73chq": specific
#: humidity q at the 13 levels)
SURFACE_VARS = ("u10m", "v10m", "u100m", "v100m", "t2m", "sp", "msl",
                "tcwv")


def channel_names(n_levels: int = 13) -> list[str]:
    """State channel order: [13*z, 13*t, 13*u, 13*v, 13*q, surface...]."""
    levels = PRESSURE_LEVELS[:n_levels]
    return ([f"{v}{p}" for v in ATMOS_VARS for p in levels]
            + list(SURFACE_VARS))


def sfno_full() -> SFNOConfig:
    """makani's sfno_linear_73chq_sc3_layers8_edim384: 721x1440, latent
    240x480 (scale factor 3), 8 blocks, embedding 384."""
    return SFNOConfig()


def sfno_smoke() -> SFNOConfig:
    """Reduced variant for CPU tests: 3 blocks (a first, a middle and a
    last, so both resampling blocks and a latent one run), tiny grids."""
    return SFNOConfig(nlat=33, nlon=64, latent_nlat=12, latent_nlon=24,
                      lmax=12, n_levels=2, embed_dim=16, n_blocks=3,
                      mlp_hidden=32)


#: Named SFNO configs; names are unique across families
#: (``repro.configs.registry``).
NAMED_CONFIGS = {
    "sfno_smoke": sfno_smoke,
    "sfno_full": sfno_full,
}

#: the family's model class (``repro.configs.registry``)
MODEL = SFNO
