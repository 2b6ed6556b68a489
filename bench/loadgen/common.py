"""What every generator shares: request records and seeded draws."""

from __future__ import annotations

import random


def new_record(due: float, spec: dict) -> dict:
    """One request: when it was due, its spec, and what came back."""
    return {"due": due, "sent": None, "spec": spec, "events": [],
            "error": None, "closed_early": False}


def draw_spec(base: dict, draw: dict, rng: random.Random) -> dict:
    """``base`` with each key of ``draw`` set to an integer in
    [0, draw[key]) from ``rng``."""
    return {**base, **{k: rng.randrange(int(n)) for k, n in draw.items()}}
