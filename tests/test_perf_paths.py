"""Tests for the beyond-paper performance paths (EXPERIMENTS.md §Perf).

Covers the DFT-as-GEMM longitude transforms, the affine band-slice gather
and the scatter/shard_map MoE dispatch -- each must be numerically
equivalent to its reference path.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.sphere import disco, fourier, grids, sht

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestFourierModes:
    def teardown_method(self):
        fourier.set_mode("fft")

    @settings(max_examples=10, deadline=None)
    @given(w=st.sampled_from([8, 16, 64, 90, 720]),
           seed=st.integers(0, 2**31 - 1))
    def test_matmul_matches_fft(self, w, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (3, w))
        fourier.set_mode("fft")
        a = fourier.rfft(x)
        xa = fourier.irfft(a, w)
        fourier.set_mode("matmul")
        b = fourier.rfft(x)
        xb = fourier.irfft(b, w)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
        np.testing.assert_allclose(np.asarray(xa), np.asarray(xb),
                                   atol=1e-5)

    def test_sht_roundtrip_in_matmul_mode(self):
        fourier.set_mode("matmul")
        g = grids.make_grid(24, 48, "gauss")
        t = sht.SHT.create(g)
        x = jax.random.normal(jax.random.PRNGKey(0), (24, 48))
        xb = t.inverse(t.forward(x))
        xbb = t.inverse(t.forward(xb))
        np.testing.assert_allclose(np.asarray(xbb), np.asarray(xb),
                                   atol=1e-4)

    def test_odd_length(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 15))
        fourier.set_mode("matmul")
        a = fourier.rfft(x)
        xa = fourier.irfft(a, 15)
        fourier.set_mode("fft")
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(fourier.rfft(x)), atol=1e-5)
        np.testing.assert_allclose(np.asarray(xa), np.asarray(x), atol=1e-5)


def _full_irdft_mats(n):
    """The n//2+1-row inverse DFT matrices written out directly: order 0
    and, for even n, the Nyquist order (the last row) once, every other
    order twice."""
    nf = n // 2 + 1
    ang = 2.0 * np.pi * np.arange(nf)[:, None] * np.arange(n)[None, :] / n
    mult = np.full((nf, 1), 2.0)
    mult[0] = 1.0
    if n % 2 == 0:
        mult[-1] = 1.0
    return ((mult * np.cos(ang) / n).astype(np.float32),
            (-mult * np.sin(ang) / n).astype(np.float32))


@pytest.mark.parametrize("mode", ["fft", "matmul"])
@pytest.mark.parametrize("n,k", [(16, 1), (16, 5), (16, 9),
                                 (15, 1), (15, 5), (15, 8)])
def test_irfft_short_spectrum_is_the_zero_padded_one(mode, n, k):
    """irfft of k <= n//2+1 orders is irfft of those orders zero-padded
    to n//2+1; at k = n//2+1 on an even n the last row is the Nyquist
    order and weighs once (numpy's irfft is the oracle), and the
    matrices are the full ones bit for bit."""
    key = jax.random.PRNGKey(n * 100 + k)
    c = jax.lax.complex(*jax.random.normal(key, (2, 3, n // 2 + 1)))[..., :k]
    padded = jnp.pad(c, ((0, 0), (0, n // 2 + 1 - k)))
    fourier.set_mode(mode)
    try:
        got = np.asarray(fourier.irfft(c, n))
        full = np.asarray(fourier.irfft(padded, n))
    finally:
        fourier.set_mode("fft")
    assert got.shape == (3, n)
    want = np.fft.irfft(np.asarray(padded, np.complex128), n=n)
    np.testing.assert_allclose(got, full, atol=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-6)
    a, b = fourier._irdft_mats(n, k)
    fa, fb = _full_irdft_mats(n)
    assert a.shape == b.shape == (k, n)
    if k == n // 2 + 1:
        np.testing.assert_array_equal(a, fa)
        np.testing.assert_array_equal(b, fb)
    else:
        np.testing.assert_array_equal(a, fa[:k])
        np.testing.assert_array_equal(b, fb[:k])


@pytest.mark.parametrize("nlat,nlon,kind,m", [(31, 64, "equiangular", 10),
                                              (24, 48, "gauss", 8)])
def test_sht_roundtrip_keeping_fewer_orders_in_matmul_mode(nlat, nlon,
                                                           kind, m):
    """An SHT that keeps m < nlon//2+1 orders synthesizes from m DFT rows:
    the round trip (inverse, then forward) equals the FFT path's, which
    zero-pads the spectrum, and on the Gauss grid, whose quadrature is
    exact, it gives band-limited coefficients back."""
    t = sht.SHT.create(grids.make_grid(nlat, nlon, kind), lmax=m, mmax=m)
    assert t.mmax < nlon // 2 + 1
    re, im = jax.random.normal(jax.random.PRNGKey(m), (2, 2, m, m))
    c = jax.lax.complex(re, im.at[..., 0].set(0.0))
    c = c * jnp.asarray(sht.mode_mask(m, m))
    trips = {}
    for mode in ("fft", "matmul"):
        fourier.set_mode(mode)
        try:
            x = t.inverse(c)
            trips[mode] = (np.asarray(x), np.asarray(t.forward(x)))
        finally:
            fourier.set_mode("fft")
    x, back = trips["matmul"]
    assert x.shape == (2, nlat, nlon)
    np.testing.assert_allclose(x, trips["fft"][0], atol=1e-5)
    np.testing.assert_allclose(back, trips["fft"][1], atol=1e-5)
    if kind == "gauss":
        np.testing.assert_allclose(back, np.asarray(c), atol=1e-4)


class TestAffineBandGather:
    @pytest.mark.parametrize("gi,go", [
        ((64, 128, "equiangular"), (32, 64, "gauss")),
        ((33, 64, "equiangular"), (16, 32, "gauss")),
        ((16, 32, "gauss"), (16, 32, "gauss")),
        ((33, 64, "equiangular"), (33, 64, "equiangular")),
    ])
    def test_affine_equals_take(self, gi, go):
        a = grids.make_grid(*gi)
        b = grids.make_grid(*go)
        plan = disco.make_disco_plan(a, b)
        assert plan.affine is not None  # every tensor-product pair is affine
        x = jax.random.normal(jax.random.PRNGKey(0), (2, a.nlat, a.nlon))
        t = disco.disco_conv(x, jnp.asarray(plan.psi),
                             jnp.asarray(plan.lat_idx), plan.stride, None)
        f = disco.disco_conv(x, jnp.asarray(plan.psi),
                             jnp.asarray(plan.lat_idx), plan.stride,
                             plan.affine)
        np.testing.assert_allclose(np.asarray(f), np.asarray(t), atol=1e-5)


def test_moe_scatter_matches_dense_subprocess():
    """Scatter dispatch == dense dispatch (values + grads) on 8 devices.

    Runs in a subprocess: shard_map needs a multi-device mesh set before
    jax initializes.
    """
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.models import moe as moelib
mesh = jax.make_mesh((4, 2), ("data", "model"))
jax.set_mesh(mesh)
cfg_d = moelib.MoEConfig(d_model=32, d_ff=16, n_experts=8, top_k=2,
                         n_shared=1, capacity_factor=2.0)
cfg_s = dataclasses.replace(cfg_d, dispatch="scatter", dp_axes=("data",))
p = moelib.init_moe(jax.random.PRNGKey(0), cfg_d)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
yd, _ = jax.jit(lambda p, x: moelib.apply_moe(p, cfg_d, x))(p, x)
ys, _ = jax.jit(lambda p, x: moelib.apply_moe(p, cfg_s, x))(p, x)
assert float(jnp.abs(yd - ys).max()) < 1e-5
gd = jax.jit(jax.grad(lambda p: moelib.apply_moe(p, cfg_d, x)[0].sum()))(p)
gs = jax.jit(jax.grad(lambda p: moelib.apply_moe(p, cfg_s, x)[0].sum()))(p)
for a, b in zip(jax.tree_util.tree_leaves(gd), jax.tree_util.tree_leaves(gs)):
    assert float(jnp.abs(a - b).max()) < 1e-4
# decode-shaped input (T < n_dp) silently falls back to the dense path
small = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 32))
y1, _ = jax.jit(lambda p, x: moelib.apply_moe(p, cfg_s, x))(p, small)
y0, _ = jax.jit(lambda p, x: moelib.apply_moe(p, cfg_d, x))(p, small)
assert float(jnp.abs(y1 - y0).max()) < 1e-5
print("MOE_SCATTER_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "MOE_SCATTER_OK" in out.stdout
