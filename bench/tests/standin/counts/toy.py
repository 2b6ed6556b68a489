"""Counts of the stand-in model family (``standin/reference/toy.py``):
one kernel family, ``toy_kernel``, called three times a step."""

from __future__ import annotations

import dataclasses

VALUE_BYTES = {"float32": 4, "bfloat16": 2}
SCOPES = ("toy.block",)


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def min_seconds(self, peaks: dict) -> tuple[float, str]:
        t_f = self.flops / peaks["flops_per_s"]
        t_b = self.bytes / peaks["hbm_bytes_per_s"]
        return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


def step_calls(m: dict, value_bytes: int = 4
               ) -> dict[str, list[tuple[Work, int]]]:
    points = m["nlat"] * m["nlon"]
    mix = Work(2.0 * m["channels"] ** 2 * points,
               value_bytes * (2 * m["channels"] * points + m["channels"] ** 2))
    return {"toy_kernel": [(mix, 3)]}


def member_step(m: dict, value_bytes: int = 4) -> Work:
    flops = nbytes = 0.0
    for calls in step_calls(m, value_bytes).values():
        for w, n in calls:
            flops, nbytes = flops + w.flops * n, nbytes + w.bytes * n
    return Work(flops, nbytes)
