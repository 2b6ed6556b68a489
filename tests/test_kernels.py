"""Per-kernel allclose validation against pure-jnp oracles (interpret mode).

Each Pallas kernel is swept over shapes/dtypes and asserted against its
ref.py oracle, plus hypothesis property sweeps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.sphere import disco as dlib
from repro.core.sphere import grids, sht
from repro.kernels.crps.crps import crps_fused
from repro.kernels.crps.ops import crps_pointwise_pallas
from repro.kernels.crps.ref import crps_fused_ref
from repro.kernels.disco.disco import disco_band_contract
from repro.kernels.disco.ref import disco_band_contract_ref
from repro.kernels.disco import ops as disco_ops
from repro.kernels.legendre.legendre import legendre_contract
from repro.kernels.legendre import ops as leg_ops
from repro.kernels.legendre.ref import legendre_contract_ref


class TestLegendreKernel:
    @pytest.mark.parametrize("shape", [
        (1, 7, 5, 3),        # tiny, heavy padding
        (4, 33, 17, 20),     # odd sizes
        (2, 128, 128, 8),    # exactly one block
        (130, 150, 96, 17),  # multi-block with remainders
        (3, 721, 360, 12),   # production-latitude scale
    ])
    def test_matches_oracle(self, shape):
        b, k, n, m = shape
        rng = np.random.default_rng(hash(shape) % 2**31)
        x = jnp.asarray(rng.normal(size=(b, k, m)), jnp.float32)
        t = jnp.asarray(rng.normal(size=(m, k, n)), jnp.float32)
        got = legendre_contract(x, t)
        ref = legendre_contract_ref(x, t)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-3 * np.sqrt(k), rtol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 40, 6)), dtype)
        t = jnp.asarray(rng.normal(size=(6, 40, 30)), dtype)
        got = legendre_contract(x, t)
        ref = legendre_contract_ref(x, t)
        assert got.dtype == jnp.float32  # fp32 accumulation
        tol = 1e-4 if dtype == jnp.float32 else 0.15
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=tol * 7, rtol=tol)

    @settings(max_examples=10, deadline=None)
    @given(b=st.integers(1, 9), k=st.integers(1, 64), n=st.integers(1, 64),
           m=st.integers(1, 10), seed=st.integers(0, 2**31 - 1))
    def test_property_sweep(self, b, k, n, m, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(b, k, m)), jnp.float32)
        t = jnp.asarray(rng.normal(size=(m, k, n)), jnp.float32)
        np.testing.assert_allclose(np.asarray(legendre_contract(x, t)),
                                   np.asarray(legendre_contract_ref(x, t)),
                                   atol=1e-3, rtol=1e-4)

    def test_pallas_sht_roundtrip(self):
        # The Pallas-backed SHT reproduces the exact XLA SHT.
        g = grids.make_grid(32, 64, "gauss")
        t = sht.SHT.create(g)
        bufs = t.buffers()
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 32, 64))
        np.testing.assert_allclose(
            np.asarray(leg_ops.sht_forward_pallas(x, bufs["wpct"])),
            np.asarray(t.forward(x)), atol=1e-5)
        c = t.forward(x)
        np.testing.assert_allclose(
            np.asarray(leg_ops.sht_inverse_pallas(c, bufs["pct"], 64)),
            np.asarray(t.inverse(c)), atol=1e-4)


def _disco_case(rng, n, r, h_in, w, k, h_out, s, d, q):
    x = jnp.asarray(rng.normal(size=(n, r, h_in, w)), jnp.float32)
    psi = jnp.asarray(rng.normal(size=(k, h_out, s, d)), jnp.float32)
    mix = jnp.asarray(rng.normal(size=(k, q, r)), jnp.float32)
    return x, psi, mix


class TestDiscoKernel:
    @pytest.mark.parametrize("shape", [
        # (N, R, H_in, W, K, H_out, S, D, Q, stride, affine, off0)
        (2, 3, 10, 32, 5, 8, 3, 7, 4, 1, (1, -1), -3),
        (3, 4, 20, 64, 7, 10, 4, 11, 6, 2, (2, -1), -5),
        (1, 2, 5, 16, 2, 5, 2, 4, 3, 1, (1, 0), 0),
        (2, 9, 34, 128, 7, 17, 5, 21, 5, 2, (2, -2), -10),
        (2, 1, 12, 64, 3, 12, 1, 64, 2, 1, (1, 0), 0),  # D == W
        (1, 140, 6, 40, 2, 6, 2, 5, 3, 1, (1, 0), -2),  # two channel tiles
    ])
    def test_matches_oracle(self, shape):
        n, r, h_in, w, k, h_out, s, d, q, stride, affine, off0 = shape
        rng = np.random.default_rng(hash(shape) % 2**31)
        x, psi, mix = _disco_case(rng, n, r, h_in, w, k, h_out, s, d, q)
        got = disco_band_contract(x, psi, mix, stride=stride, affine=affine,
                                  off0=off0)
        ref = disco_band_contract_ref(x, psi, mix, stride=stride,
                                      affine=affine, off0=off0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4 * np.sqrt(s * d * k * r),
                                   rtol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(1, 3), h=st.integers(1, 12), s=st.integers(1, 4),
           wp=st.integers(3, 6), k=st.integers(1, 4),
           seed=st.integers(0, 2**31 - 1))
    def test_property_sweep(self, n, h, s, wp, k, seed):
        w = 2 ** wp
        rng = np.random.default_rng(seed)
        d = int(rng.integers(1, w))
        r, q = int(rng.integers(1, 4)), int(rng.integers(1, 4))
        x, psi, mix = _disco_case(rng, n, r, h + s, w, k, h, s, d, q)
        np.testing.assert_allclose(
            np.asarray(disco_band_contract(x, psi, mix)),
            np.asarray(disco_band_contract_ref(x, psi, mix)),
            atol=1e-3, rtol=1e-4)

    def test_banded_equals_fft_path_on_real_plan(self):
        # The Pallas band path reproduces the exact FFT DISCO convolution
        # for a real encoder plan (equiangular -> Gaussian downsampling).
        gi = grids.make_grid(64, 128, "equiangular")
        go = grids.make_grid(32, 64, "gauss")
        plan = dlib.make_disco_plan(gi, go)
        band, off0, exact = disco_ops.banded_psi_from_plan(plan)
        assert exact
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 128))
        fft_out = dlib.disco_conv(x, jnp.asarray(plan.psi),
                                  jnp.asarray(plan.lat_idx), plan.stride)
        # identity mix: the kernel's output rows are the K basis responses
        k = band.shape[0]
        mix = jnp.eye(k, dtype=jnp.float32)[:, :, None]
        band_out = disco_band_contract(
            x[:, None], jnp.asarray(band), mix, stride=plan.stride,
            affine=plan.affine, off0=off0)
        np.testing.assert_allclose(np.asarray(band_out), np.asarray(fft_out),
                                   atol=1e-5)


class TestCRPSKernel:
    @pytest.mark.parametrize("e", [1, 2, 3, 8, 16])
    @pytest.mark.parametrize("n", [1, 100, 1024, 5000])
    @pytest.mark.parametrize("fair", [False, True])
    def test_matches_oracle(self, e, n, fair):
        if fair and e == 1:
            pytest.skip("fair CRPS undefined for E=1")
        rng = np.random.default_rng(e * 7919 + n)
        ens = jnp.asarray(rng.normal(size=(e, n)), jnp.float32)
        obs = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        got = crps_fused(ens, obs, fair=fair)
        ref = crps_fused_ref(ens, obs, fair=fair)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_multidim_wrapper(self):
        rng = np.random.default_rng(1)
        ens = jnp.asarray(rng.normal(size=(4, 2, 3, 8, 16)), jnp.float32)
        obs = jnp.asarray(rng.normal(size=(2, 3, 8, 16)), jnp.float32)
        got = crps_pointwise_pallas(ens, obs)
        ref = crps_fused_ref(ens.reshape(4, -1), obs.reshape(-1))
        np.testing.assert_allclose(np.asarray(got).ravel(), np.asarray(ref),
                                   atol=1e-5)

    @settings(max_examples=15, deadline=None)
    @given(e=st.integers(2, 12), n=st.integers(1, 300),
           seed=st.integers(0, 2**31 - 1), fair=st.booleans())
    def test_property_sweep(self, e, n, seed, fair):
        rng = np.random.default_rng(seed)
        scale = 10.0 ** rng.integers(-2, 3)
        ens = jnp.asarray(rng.normal(size=(e, n)) * scale, jnp.float32)
        obs = jnp.asarray(rng.normal(size=(n,)) * scale, jnp.float32)
        got = crps_fused(ens, obs, fair=fair)
        ref = crps_fused_ref(ens, obs, fair=fair)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5 * scale, rtol=1e-4)
