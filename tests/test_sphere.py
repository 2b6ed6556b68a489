"""Tests for the spherical signal-processing substrate (paper Appendix B)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.sphere import disco, grids, interp, legendre, noise, sht, spectral_conv


# ---------------------------------------------------------------------------
# Grids & quadrature (B.1)
# ---------------------------------------------------------------------------

class TestGrids:
    @pytest.mark.parametrize("kind", ["equiangular", "gauss"])
    def test_weights_positive_and_sum_to_sphere_area(self, kind):
        g = grids.make_grid(37, 72, kind)
        assert (g.quad_weights > 0).all()
        total = g.cell_area.sum() * g.nlon
        np.testing.assert_allclose(total, 4 * np.pi, rtol=1e-10)

    def test_gauss_exact_for_polynomials(self):
        # GL quadrature integrates cos(theta)^k exactly for k <= 2n-1.
        g = grids.make_grid(8, 16, "gauss")
        for k in range(0, 15):
            f = np.cos(g.colat)[:, None] ** k * np.ones((1, g.nlon))
            got = grids.quad_integrate(g, f)
            exact = 2 * np.pi * (1 + (-1) ** k) / (k + 1)
            np.testing.assert_allclose(got, exact, atol=1e-12)

    def test_equiangular_includes_poles(self):
        g = grids.make_grid(721, 1440, "equiangular")
        assert g.colat[0] == 0.0 and np.isclose(g.colat[-1], np.pi)


# ---------------------------------------------------------------------------
# Legendre & SHT (B.3)
# ---------------------------------------------------------------------------

class TestSHT:
    def test_legendre_orthonormal_on_gauss(self):
        g = grids.make_grid(24, 48, "gauss")
        p = legendre.legendre_table(24, 24, g.colat)
        for m in [0, 1, 5]:
            gram = np.einsum("h,hl,hk->lk", g.quad_weights,
                             p[:, :, m], p[:, :, m]) * 2 * np.pi
            valid = np.arange(24) >= m
            expect = np.diag(valid.astype(float))
            np.testing.assert_allclose(gram, expect, atol=1e-10)

    def test_roundtrip_gauss_exact(self):
        g = grids.make_grid(32, 64, "gauss")
        t = sht.SHT.create(g)
        rng = np.random.default_rng(0)
        c = (rng.normal(size=(t.lmax, t.mmax))
             + 1j * rng.normal(size=(t.lmax, t.mmax)))
        c *= sht.mode_mask(t.lmax, t.mmax)
        c[:, 0] = c[:, 0].real
        x = t.inverse(jnp.asarray(c, jnp.complex64))
        c2 = np.asarray(t.forward(x))
        np.testing.assert_allclose(c2, c, atol=2e-5)

    def test_parseval(self):
        g = grids.make_grid(32, 64, "gauss")
        t = sht.SHT.create(g)
        x = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
        c = t.forward(x)
        x_bl = t.inverse(c)  # band-limited projection of x
        integ = grids.quad_integrate(g, np.asarray(x_bl) ** 2)
        np.testing.assert_allclose(
            integ, np.asarray(sht.spectrum(c)).sum(), rtol=1e-5)

    def test_convolution_theorem(self):
        # Zonal filter acts diagonally: isht(sht(x) * k_l) equals the
        # continuous group convolution with the axisymmetric filter.
        g = grids.make_grid(24, 48, "gauss")
        t = sht.SHT.create(g)
        key = jax.random.PRNGKey(2)
        x = t.inverse(t.forward(jax.random.normal(key, (24, 48))))
        kl = jnp.exp(-0.05 * jnp.arange(t.lmax) ** 2)
        y = t.inverse(t.forward(x) * kl[:, None])
        # Rotation about the z axis commutes with the zonal convolution:
        shift = 7
        y_rot = t.inverse(t.forward(jnp.roll(x, shift, axis=-1)) * kl[:, None])
        np.testing.assert_allclose(np.asarray(jnp.roll(y, shift, axis=-1)),
                                   np.asarray(y_rot), atol=1e-4)

    def test_resample_preserves_bandlimited(self):
        g1 = grids.make_grid(24, 48, "gauss")
        g2 = grids.make_grid(48, 96, "gauss")
        t1, t2 = sht.SHT.create(g1), sht.SHT.create(g2)
        x = t1.inverse(t1.forward(jax.random.normal(jax.random.PRNGKey(3), (24, 48))))
        up = sht.resample(x, t1, t2)
        back = sht.resample(up, t2, t1)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(nlat=st.integers(8, 24), seed=st.integers(0, 2**31 - 1))
    def test_roundtrip_property(self, nlat, seed):
        g = grids.make_grid(nlat, 2 * nlat, "gauss")
        t = sht.SHT.create(g)
        x = jax.random.normal(jax.random.PRNGKey(seed), (nlat, 2 * nlat))
        xb = t.inverse(t.forward(x))
        xbb = t.inverse(t.forward(xb))
        np.testing.assert_allclose(np.asarray(xbb), np.asarray(xb),
                                   atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# DISCO convolutions (B.5)
# ---------------------------------------------------------------------------

class TestDisco:
    def setup_method(self):
        self.gi = grids.make_grid(64, 128, "equiangular")
        self.go = grids.make_grid(32, 64, "gauss")
        self.plan = disco.make_disco_plan(self.gi, self.go)

    def test_shapes(self):
        assert self.plan.psi.shape[0] == self.plan.n_basis == 7
        assert self.plan.stride == 2
        x = jnp.ones((64, 128))
        z = disco.disco_conv(x, jnp.asarray(self.plan.psi),
                             jnp.asarray(self.plan.lat_idx), self.plan.stride)
        assert z.shape == (7, 32, 64)

    def test_zonal_symmetry_on_constant(self):
        # A constant field convolved with any filter must be zonally constant.
        x = jnp.ones((64, 128))
        z = disco.disco_conv(x, jnp.asarray(self.plan.psi),
                             jnp.asarray(self.plan.lat_idx), self.plan.stride)
        assert float(jnp.std(z, axis=-1).max()) < 1e-4

    def test_localization(self):
        # Delta input produces response only within the cutoff radius.
        d = jnp.zeros((64, 128)).at[32, 64].set(1.0)
        r = disco.disco_conv(d, jnp.asarray(self.plan.psi),
                             jnp.asarray(self.plan.lat_idx), self.plan.stride)
        r = np.asarray(jnp.abs(r).sum(axis=0))
        src = self.gi.colat[32]
        for h in range(32):
            if abs(self.go.colat[h] - src) > 2.5 * self.plan.theta_cutoff:
                assert r[h].max() < 1e-6, h

    def test_longitude_equivariance(self):
        # Rotation about z commutes with the spherical group convolution.
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (64, 128))
        buf = self.plan.buffers()
        z1 = disco.disco_conv(jnp.roll(x, 4, axis=-1), buf["psi"],
                              buf["lat_idx"], self.plan.stride)
        z2 = jnp.roll(disco.disco_conv(x, buf["psi"], buf["lat_idx"],
                                       self.plan.stride), 2, axis=-1)
        np.testing.assert_allclose(np.asarray(z1), np.asarray(z2), atol=1e-4)

    def test_grouped_channel_separation(self):
        # groups == c_in: output channel o only depends on input channel o//k.
        key = jax.random.PRNGKey(1)
        p = disco.init_disco_conv(key, 4, 4, self.plan.n_basis, groups=4,
                                  bias=False)
        x = jax.random.normal(key, (1, 4, 64, 128))
        y0 = disco.apply_disco_conv(p, x, self.plan.buffers(),
                                    self.plan.stride, groups=4)
        x2 = x.at[:, 1].set(0.0)
        y1 = disco.apply_disco_conv(p, x2, self.plan.buffers(),
                                    self.plan.stride, groups=4)
        np.testing.assert_allclose(np.asarray(y0[:, 0]), np.asarray(y1[:, 0]),
                                   atol=1e-6)
        assert float(jnp.abs(y0[:, 1] - y1[:, 1]).max()) > 1e-3

    @pytest.mark.parametrize("lead,c_in,c_out,groups", [
        ((), 5, 3, 1),        # input-channel chunks of 2: one padded
        ((), 4, 4, 2),        # grouped: whole contraction per call
        ((2, 2), 5, 3, 1),    # leading dims mapped, then chunked
        ((2,), 4, 6, 2),      # leading dims mapped, grouped
    ])
    def test_memory_bounded_reference_matches_whole(self, monkeypatch, lead,
                                                    c_in, c_out, groups):
        # the pieces the reference contracts in above _REF_BAND_ELEMS
        # (full width) must sum to the one-shot contraction
        key = jax.random.PRNGKey(2)
        w = disco.init_disco_conv(key, c_out, c_in, self.plan.n_basis,
                                  groups=groups, bias=False)["weight"]
        x = jax.random.normal(key, lead + (c_in, 64, 128))
        buf = self.plan.buffers()
        args = (w, x, buf["psi"], buf["lat_idx"], self.plan.stride, groups,
                self.plan.affine)
        whole = disco._reference_conv(*args)
        per_channel = 128 * self.plan.psi.shape[1] * self.plan.psi.shape[2]
        monkeypatch.setattr(disco, "_REF_BAND_ELEMS", 2 * per_channel)
        pieces = disco._reference_conv(*args)
        assert pieces.shape == whole.shape == lead + (c_out, 32, 64)
        np.testing.assert_allclose(np.asarray(pieces), np.asarray(whole),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Interpolation (B.6)
# ---------------------------------------------------------------------------

class TestInterp:
    def test_identity_on_same_grid(self):
        g = grids.make_grid(16, 32, "equiangular")
        r = interp.BilinearResample.create(g, g)
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 16, 32))
        np.testing.assert_allclose(np.asarray(r(x)), np.asarray(x), atol=1e-6)

    def test_exact_for_bilinear_functions(self):
        # f(theta, phi) linear in theta & phi-independent is reproduced.
        gi = grids.make_grid(33, 64, "equiangular")
        go = grids.make_grid(21, 32, "gauss")
        f = jnp.asarray(gi.colat)[:, None] * jnp.ones((1, 64))
        r = interp.BilinearResample.create(gi, go)
        out = np.asarray(r(f))
        np.testing.assert_allclose(out, go.colat[:, None] * np.ones((1, 32)),
                                   rtol=1e-5)

    def test_constant_preserved_with_pole_handling(self):
        gi = grids.make_grid(20, 40, "gauss")  # no pole rows
        go = grids.make_grid(41, 80, "equiangular")  # has pole rows
        r = interp.BilinearResample.create(gi, go)
        out = np.asarray(r(jnp.ones((20, 40))))
        np.testing.assert_allclose(out, 1.0, atol=1e-6)


# ---------------------------------------------------------------------------
# Spherical diffusion noise (B.7)
# ---------------------------------------------------------------------------

class TestNoise:
    def setup_method(self):
        g = grids.make_grid(24, 48, "gauss")
        self.sd = noise.SphericalDiffusion(sht=sht.SHT.create(g),
                                           k_t=(1e-3, 1e-2), lam=1.0)

    def test_stationary_variance(self):
        # Paper (28): stationary pointwise std is sigma/sqrt(2) under the
        # orthonormal-harmonics convention used here.
        z = self.sd.to_grid(self.sd.init_state(jax.random.PRNGKey(0), (256,)))
        std = float(jnp.std(z[:, 0]))
        assert abs(std - 1 / np.sqrt(2)) < 0.05, std

    def test_ar1_temporal_correlation(self):
        key = jax.random.PRNGKey(1)
        s = self.sd.init_state(key, (512,))
        s2 = self.sd.step(jax.random.PRNGKey(2), s)
        z1 = np.asarray(self.sd.to_grid(s)).ravel()
        z2 = np.asarray(self.sd.to_grid(s2)).ravel()
        corr = np.corrcoef(z1, z2)[0, 1]
        np.testing.assert_allclose(corr, np.exp(-1.0), atol=0.05)

    def test_length_scales_order(self):
        # larger k_T -> smoother field -> smaller mean squared gradient proxy.
        z = self.sd.to_grid(self.sd.init_state(jax.random.PRNGKey(3), (64,)))
        rough = [float(jnp.mean(jnp.diff(z[:, i], axis=-1) ** 2))
                 for i in range(2)]
        assert rough[0] > rough[1]

    def test_noise_centering(self):
        z = self.sd.to_grid(self.sd.init_state(jax.random.PRNGKey(4), (4,)))
        c = noise.center_noise(z, axis=0)
        np.testing.assert_allclose(np.asarray(c[1]), -np.asarray(c[0]))
        np.testing.assert_allclose(np.asarray(c[0]), np.asarray(z[0]))
        np.testing.assert_allclose(np.asarray(c[3]), -np.asarray(z[2]))


class TestSpectralConv:
    def test_depthwise_is_diagonal(self):
        g = grids.make_grid(16, 32, "gauss")
        t = sht.SHT.create(g)
        p = spectral_conv.init_spectral_filter(jax.random.PRNGKey(0), 3, 3,
                                               t.lmax, mode="depthwise")
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 32))
        y = spectral_conv.apply_spectral_conv(p, x, t.buffers(), 32)
        # identity gains => band-limited projection of x
        xb = t.inverse(t.forward(x))
        np.testing.assert_allclose(np.asarray(y), np.asarray(xb), atol=1e-4)

    def test_full_mixing_shape_and_scale(self):
        g = grids.make_grid(16, 32, "gauss")
        t = sht.SHT.create(g)
        p = spectral_conv.init_spectral_filter(jax.random.PRNGKey(0), 8, 4,
                                               t.lmax)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16, 32))
        y = spectral_conv.apply_spectral_conv(p, x, t.buffers(), 32)
        assert y.shape == (2, 8, 16, 32)
        assert 0.2 < float(y.std()) < 5.0
