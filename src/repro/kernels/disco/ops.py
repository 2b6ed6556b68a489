"""Host-side helpers for the Pallas DISCO band kernel.

``banded_psi_from_plan`` extracts the (K, H, S, D) band (and checks it is
exact) from a DiscoPlan.  The jitted model path is
``repro.kernels.dispatch.disco_conv_mixed``.
"""

from __future__ import annotations

import numpy as np

from repro.core.sphere.disco import DiscoPlan


def banded_psi_from_plan(plan: DiscoPlan, d_max: int | None = None
                         ) -> tuple[np.ndarray, int, bool]:
    """Extract the banded filter tensor from a plan.

    The full psi stores every longitudinal offset (zero beyond the geodesic
    cutoff).  The band keeps offsets dw in (-D/2, D/2] re-indexed to
    [0, D) via the wrap ``dw mod W``; the first (D+1)//2 taps map to
    positive offsets, the tail to negative ones.

    Returns (psi_band with shape (K, H, S, D), D, exact) where ``exact``
    is True iff no nonzero psi entry lies outside the band.
    """
    psi = plan.psi  # (K, H, S, W)
    k, h, s, w = psi.shape
    nz = np.abs(psi).max(axis=(0, 2))  # (H, W)
    # support mask per output row over offsets; offsets are 0..W-1 circular.
    half = w // 2
    shifted = np.concatenate([nz[:, half:], nz[:, :half]], axis=1)  # center 0
    cols = np.where(shifted.max(axis=0) > 0)[0]
    if cols.size == 0:
        lo, hi = half, half
    else:
        lo, hi = cols.min(), cols.max()
    d = int(hi - lo + 1)
    if d_max is not None:
        d = min(d, d_max)
    # band offsets relative to 0: [lo-half, hi-half]
    off0 = lo - half
    idx = (np.arange(d) + off0) % w
    band = psi[:, :, :, idx]
    # exact iff NO nonzero psi entry falls outside the band columns --
    # checked structurally (a float-sum comparison would miss truncated
    # entries smaller than the tolerance).
    outside = np.ones(w, bool)
    outside[idx] = False
    exact = not np.any(psi[:, :, :, outside])
    return band.astype(np.float32), int(off0), exact
