"""The service's named configurations of the stand-in model family."""

import dataclasses


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    channels: int = 3
    nlat: int = 4
    nlon: int = 8


NAMED_CONFIGS = {"tiny": ToyConfig}
