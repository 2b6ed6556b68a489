"""Tests for the FCN3 model (paper Section 3 / Appendix C)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import fcn3 as cfgs
from repro.core import blocks as blk
from repro.core.fcn3 import FCN3, FCN3Config


@pytest.fixture(scope="module")
def tiny():
    cfg = cfgs.fcn3_smoke()
    model = FCN3(cfg)
    params = model.init(jax.random.PRNGKey(0))
    buffers = model.make_buffers()
    return cfg, model, params, buffers


def _inputs(cfg, model, batch=2, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    state = jax.random.normal(k1, (batch, cfg.n_state, cfg.nlat, cfg.nlon))
    aux = jax.random.normal(k2, (batch, cfg.n_aux, cfg.nlat, cfg.nlon))
    z = model.sample_noise(k3, (batch,))
    return state, jnp.concatenate([aux, z], axis=1)


class TestFCN3Forward:
    def test_output_shape_and_finite(self, tiny):
        cfg, model, params, buffers = tiny
        state, cond = _inputs(cfg, model)
        out = jax.jit(model.apply)(params, buffers, state, cond)
        assert out.shape == state.shape
        assert bool(jnp.isfinite(out).all())

    def test_water_channels_nonnegative(self, tiny):
        # Output transformation C.8: softclamped water channels are >= 0.
        cfg, model, params, buffers = tiny
        state, cond = _inputs(cfg, model)
        out = model.apply(params, buffers, state, cond)
        w = cfg.water_channel_indices()
        assert float(out[:, w].min()) >= 0.0
        other = [c for c in range(cfg.n_state) if c not in set(w.tolist())]
        assert float(out[:, other].min()) < 0.0  # others untouched

    def test_clamp_water_off_returns_the_decoder_output(self, tiny):
        # apply = softclamp on the water channels of the unclamped output
        cfg, model, params, buffers = tiny
        state, cond = _inputs(cfg, model)
        out = model.apply(params, buffers, state, cond)
        raw = model.apply(params, buffers, state, cond, clamp_water=False)
        w = cfg.water_channel_indices()
        assert float(raw[:, w].min()) < 0.0
        np.testing.assert_array_equal(np.asarray(out[:, w]),
                                      np.asarray(blk.softclamp(raw[:, w])))
        other = [c for c in range(cfg.n_state) if c not in set(w.tolist())]
        np.testing.assert_array_equal(np.asarray(out[:, other]),
                                      np.asarray(raw[:, other]))

    def test_noise_changes_prediction(self, tiny):
        # Hidden Markov model: different latent noise -> different member.
        cfg, model, params, buffers = tiny
        state, cond = _inputs(cfg, model)
        z2 = model.sample_noise(jax.random.PRNGKey(99), (2,))
        cond2 = cond.at[:, cfg.n_aux:].set(z2)
        o1 = model.apply(params, buffers, state, cond)
        o2 = model.apply(params, buffers, state, cond2)
        assert float(jnp.abs(o1 - o2).max()) > 1e-4

    def test_deterministic_given_noise(self, tiny):
        cfg, model, params, buffers = tiny
        state, cond = _inputs(cfg, model)
        o1 = model.apply(params, buffers, state, cond)
        o2 = model.apply(params, buffers, state, cond)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2))

    @pytest.mark.slow
    def test_vmap_over_ensemble(self, tiny):
        # Ensemble members share params/state and differ only in noise.
        cfg, model, params, buffers = tiny
        state, cond = _inputs(cfg, model, batch=1)
        z = model.sample_noise(jax.random.PRNGKey(5), (4, 1), centered=True)
        aux = jnp.broadcast_to(cond[None, :, : cfg.n_aux],
                               (4, 1, cfg.n_aux, cfg.nlat, cfg.nlon))
        cond_e = jnp.concatenate([aux, z], axis=2)
        out = jax.vmap(lambda c: model.apply(params, buffers, state, c))(cond_e)
        assert out.shape == (4, 1, cfg.n_state, cfg.nlat, cfg.nlon)
        # centered noise => members 0/1 differ (model is nonlinear in z)
        assert float(jnp.abs(out[0] - out[1]).max()) > 1e-5

    def test_autoregressive_rollout_stable_magnitude(self, tiny):
        # Autoregressive steps at init must not blow up: the LN-free design
        # relies on calibrated init scaling (paper C.6 / Fig. 11).
        cfg, model, _, buffers = tiny
        state, cond = _inputs(cfg, model)
        params = model.init_calibrated(jax.random.PRNGKey(0), state, cond,
                                       buffers)
        s = state
        step = jax.jit(model.apply)
        for _ in range(10):
            s = step(params, buffers, s, cond)
            assert bool(jnp.isfinite(s).all())
        assert float(jnp.abs(s).max()) < 10.0


class TestArchitectureDetails:
    def test_block_pattern_is_1_global_4_local(self):
        cfg = FCN3Config()
        kinds = [s.kind for s in cfg.block_specs()]
        assert kinds == ["global"] + ["local"] * 4 + ["global"] + ["local"] * 4

    def test_full_config_dimensions(self):
        # Table 2 checks.
        cfg = cfgs.fcn3_full()
        assert (cfg.nlat, cfg.nlon) == (721, 1440)
        assert (cfg.latent_nlat, cfg.latent_nlon) == (360, 720)
        assert cfg.c_latent == 641
        assert cfg.c_latent + cfg.cond_embed == 677
        assert cfg.n_state == 72
        assert cfg.mlp_hidden == 1282

    def test_channel_table(self):
        names = cfgs.channel_names()
        assert len(names) == 72
        wc = cfgs.channel_weights()
        assert wc.shape == (72,)
        # Table 4: t2m weighted 1.0; z500 weighted 0.5
        assert wc[names.index("t2m")] == 1.0
        np.testing.assert_allclose(wc[names.index("z500")], 0.5)
        water = cfgs.water_channel_names()
        assert "tcwv" in water and "q850" in water

    def test_encoder_no_channel_mixing(self, tiny):
        # C.3: each variable is encoded separately (grouped convs). Zeroing
        # one surface variable must not change other groups' embeddings.
        cfg, model, params, buffers = tiny
        state, cond = _inputs(cfg, model, batch=1)
        z1, _ = model._encode(params, buffers, state, cond)
        state2 = state.at[:, cfg.n_levels * cfg.n_atmos].set(0.0)  # u10m
        z2, _ = model._encode(params, buffers, state2, cond)
        na = cfg.n_levels * cfg.atmos_embed
        per_var = cfg.surface_embed // cfg.n_surface
        # atmospheric embeddings unchanged
        np.testing.assert_allclose(np.asarray(z1[:, :na]),
                                   np.asarray(z2[:, :na]), atol=1e-6)
        # u10m group changed, remaining surface groups unchanged
        assert float(jnp.abs(z1[:, na:na + per_var]
                             - z2[:, na:na + per_var]).max()) > 1e-4
        np.testing.assert_allclose(np.asarray(z1[:, na + per_var:]),
                                   np.asarray(z2[:, na + per_var:]),
                                   atol=1e-6)

    def test_softclamp_properties(self):
        u = jnp.linspace(-2, 2, 101)
        y = blk.softclamp(u)
        assert float(y.min()) == 0.0
        np.testing.assert_allclose(float(blk.softclamp(jnp.asarray(0.25))),
                                   0.0625)
        np.testing.assert_allclose(float(blk.softclamp(jnp.asarray(2.0))),
                                   1.75)
        # C1 continuity at the knots
        eps = 1e-4
        for knot in (0.0, 0.5):
            d1 = (blk.softclamp(jnp.asarray(knot + eps))
                  - blk.softclamp(jnp.asarray(knot - eps))) / (2 * eps)
            d1_in = (blk.softclamp(jnp.asarray(knot + 2 * eps))
                     - blk.softclamp(jnp.asarray(knot))) / (2 * eps)
            assert abs(float(d1) - float(d1_in)) < 0.01

    def test_activation_variance_bounded(self, tiny):
        # Paper C.6/Fig. 11: without LayerNorm, activations stay bounded
        # through the processor thanks to init + LayerScale.
        cfg, model, params, buffers = tiny
        state, cond = _inputs(cfg, model)
        x, c = model._encode(params, buffers, state, cond)
        specs = cfg.block_specs()
        v0 = float(jnp.var(x))
        for p, spec in zip(params["blocks"], specs):
            buf = (buffers["latent"] if spec.kind == "local"
                   else buffers["latent_sht"])
            x = blk.apply_block(p, spec, x, c, buf)
            v = float(jnp.var(x))
            assert 0.1 * v0 < v < 10.0 * v0
