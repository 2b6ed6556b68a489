"""The comparison that decides ``correct``: served products against the
plain float32 reference (``bench.reference``) of the same requests.

A check file (``bench/checks/<workload>.json``) says how many served
requests to draw, how many leads of each to roll again, and the limit of
each number compared.  Every number is a widest gap, so larger is worse:

* ``scores_gap``: over leads, channels and the scores CRPS, ensemble-mean
  RMSE and spread-skill ratio, the largest |served - reference| /
  |reference|.
* ``spectrum_gap``: over leads and channels, the largest relative L2
  distance between the served and the reference member-mean angular
  power spectra, taken over all degrees.
"""

from __future__ import annotations

import math
import random

import numpy as np

SCORES = ("crps", "ens_rmse", "ssr")


def served_leads(rec: dict, leads: int) -> list[dict] | None:
    """The first ``leads`` leads' products of a request, or None when its
    stream did not carry them all."""
    out: dict[int, dict] = {}
    for _t, ev in rec["events"]:
        if ev.get("event") != "chunk":
            continue
        for i, n in enumerate(ev["lead_steps"]):
            out[int(n)] = {k: np.asarray(v, np.float64)[i]
                           for k, v in ev["scores"].items()}
    if any(n not in out for n in range(leads)):
        return None
    return [out[n] for n in range(leads)]


def pick(records: list[dict], n_requests: int, leads: int,
         rng: random.Random) -> list[tuple[dict, list[dict]]]:
    """A seeded sample of ``n_requests`` requests that carried their
    first ``leads`` leads."""
    ok = [(r, s) for r in records
          if (s := served_leads(r, leads)) is not None]
    rng.shuffle(ok)
    return ok[:n_requests]


def _rel(g, w, axis=None) -> np.ndarray:
    """|g - w| / |w| elementwise (or, with ``axis``, the relative L2
    distance along it), |w| floored at a millionth of the median |w| (a
    channel the softclamp zeroes has CRPS 0 on both sides); a non-finite
    served value reads as an infinite gap."""
    g = np.asarray(g, np.float64)
    w = np.asarray(w, np.float64)
    floor = 1e-6 * max(float(np.median(np.abs(w))), 1e-30)
    if axis is None:
        gap = np.abs(g - w) / np.maximum(np.abs(w), floor)
    else:
        gap = (np.linalg.norm(g - w, axis=axis)
               / np.maximum(np.linalg.norm(w, axis=axis), floor))
    return np.where(np.isfinite(gap), gap, np.inf)


def gap_table(served: list[dict], ref: list[dict]) -> dict[str, np.ndarray]:
    """Per product, the (lead, channel) gaps of one request."""
    out: dict[str, list] = {}
    for got, want in zip(served, ref):
        for name in SCORES:
            if name in want:
                out.setdefault(name, []).append(_rel(got[name], want[name]))
        if "spectrum" in want:
            out.setdefault("spectrum", []).append(
                _rel(got["spectrum"], want["spectrum"], axis=-1))
    return {k: np.stack(v) for k, v in out.items()}


def gaps(served: list[dict], ref: list[dict]) -> dict[str, float]:
    """Widest gaps of one request's leads (see the module doc)."""
    table = gap_table(served, ref)
    out = {}
    scores = [float(table[n].max()) for n in SCORES if n in table]
    if scores:
        out["scores_gap"] = max(scores)
    if "spectrum" in table:
        out["spectrum_gap"] = float(table["spectrum"].max())
    return out


def judge(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    """Correct when every limited number was read, is finite and lies
    at or under its limit."""
    for name, limit in limits.items():
        v = numbers.get(name)
        if v is None or not math.isfinite(v) or limit is None or v > limit:
            return False
    return True
