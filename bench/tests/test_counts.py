"""Hand counts of the algorithm's operations and bytes at small shapes,
for the counts the smoke configuration's family gives."""

import itertools
import json
import os

import numpy as np
import pytest

from bench import family
from bench.reference import grids as glib

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "fcn3_smoke.json")) as _f:
    SMOKE = json.load(_f)
counts = family.counts(SMOKE)


def test_legendre_counts_the_lower_triangle_only():
    # lmax 3, mmax 2: slots (l, m) with m <= l are 3 + 2 = 5
    w = counts.legendre(channels=2, nlat=4, lmax=3, mmax=2)
    rows = 2 * 2                                  # real and imaginary parts
    assert w.flops == 2 * rows * 4 * 5
    assert w.bytes == 4 * (rows * (4 * 2 + 5) + 5 * 4)


def test_disco_support_matches_brute_force():
    gi = glib.make_grid(9, 16, "equiangular")
    go = glib.make_grid(4, 8, "gauss")
    cutoff = 3.0 * np.pi / go.nlat
    taps, half = counts.disco_support(9, 16, "equiangular", 4, 8, "gauss",
                                      3.0)
    for h, to in enumerate(go.colat):
        n, widest = 0, -1
        for i, j in itertools.product(range(gi.nlat), range(gi.nlon)):
            ti, dphi = gi.colat[i], gi.lons[j]
            c = (np.cos(to) * np.cos(ti)
                 + np.sin(to) * np.sin(ti) * np.cos(dphi))
            if np.arccos(np.clip(c, -1, 1)) < cutoff:
                n += 1
                widest = max(widest, min(j, gi.nlon - j))
        assert taps[h] == n
        assert half[h] == widest


def test_disco_flops_by_hand():
    taps, half = counts.disco_support(9, 16, "equiangular", 9, 16,
                                      "equiangular", 3.0)
    w = counts.disco(c_in=3, c_out=6, groups=3, grid_in=(9, 16, "equiangular"),
                     grid_out=(9, 16, "equiangular"), cutoff_factor=3.0)
    k = counts.N_BASIS
    n_taps = taps.sum() * 16
    assert w.flops == 2 * k * 3 * n_taps + 2 * 6 * 1 * k * 9 * 16
    parts = [counts.disco(3, 6, 3, (9, 16, "equiangular"),
                          (9, 16, "equiangular"), 3.0, rows=r)
             for r in ("kernel", "wrap")]
    assert parts[0].flops + parts[1].flops == pytest.approx(w.flops)
    keep = counts.kernel_rows(half, 16)
    assert keep.any() and not keep.all()          # pole rings wrap


def test_member_step_at_smoke_shapes():
    m = SMOKE["model"]
    calls = counts.step_calls(m)
    # 2 blocks, one global every 2: one local DISCO block, one spectral
    assert [n for _w, n in calls["disco_kernel"]] == [1, 1, 1, 1, 2, 1]
    assert [n for _w, n in calls["legendre"]] == [1, 1]
    c_lat = 2 * 10 + 14
    hw = 16 * 32
    assert calls["mlp"][0][0].flops == 2.0 * 2 * c_lat * 32 * hw
    total = counts.member_step(m)
    assert total.flops == pytest.approx(sum(
        counts.total(c).flops for c in calls.values()))


def test_bytes_move_at_the_served_width():
    """bfloat16 halves every byte and leaves the operations alone."""
    m = SMOKE["model"]
    f32 = counts.member_step(m, counts.VALUE_BYTES["float32"])
    bf16 = counts.member_step(m, counts.VALUE_BYTES["bfloat16"])
    assert bf16.flops == f32.flops
    assert bf16.bytes == pytest.approx(f32.bytes / 2)


def test_min_seconds_names_the_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.Work(1000.0, 1.0).min_seconds(peaks) == (10.0, "compute")
    assert counts.Work(1.0, 1000.0).min_seconds(peaks) == (100.0, "memory")
