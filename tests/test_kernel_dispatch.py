"""Tests for the kernel-dispatch substrate (KernelConfig -> Pallas hot path).

Load-bearing guarantees:

* ``KernelConfig`` resolution is backend-aware: "auto" never selects the
  Pallas interpreter on CPU, and "pallas" on CPU requires an explicit
  ``interpret=True``;
* the banded psi split is lossless (band + wrap rows cover every nonzero
  filter entry), its row runs cover every band row once with taps that
  hold every nonzero entry of their rows, and the banded dispatch (one
  kernel call per run) reproduces the exact FFT DISCO convolution on
  real plans;
* ``FCN3.make_buffers`` under pallas dispatch materializes the banded
  layout only -- never the full (K, H, S, W) psi;
* ``FCN3.apply`` and a full ``ForecastEngine.forecast`` rollout match
  reference dispatch within fp32 tolerance, including gradients (the
  Pallas kernels carry reference-math custom VJPs);
* ``banded_psi_from_plan`` reports ``exact=False`` iff a nonzero psi
  entry falls outside the extracted band.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import fcn3 as cfgs
from repro.core.fcn3 import FCN3
from repro.core.sphere import disco as dlib
from repro.core.sphere import grids, sht
from repro.kernels import dispatch as kdispatch
from repro.kernels.config import BLOCK_DEFAULTS, KernelConfig, disco_window
from repro.kernels.disco import ops as disco_ops

#: explicit CPU-CI pallas dispatch (interpret mode); on TPU/GPU the same
#: tests would exercise the compiled kernels.
PALLAS = KernelConfig(sht="pallas", disco="pallas", interpret=True)


#: plans whose band splits into three runs: stride 1 and stride 2
RUN_PLANS = [
    ((33, 320, "equiangular"), (33, 320, "equiangular")),
    ((33, 640, "equiangular"), (33, 320, "equiangular")),
]


class TestKernelConfig:
    def test_auto_resolution_is_backend_aware(self):
        kc = KernelConfig()
        compiled = jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")
        for op in ("sht", "disco"):
            path, interpret = kc.resolve(op)
            if compiled:
                assert (path, interpret) == ("pallas", False)
            else:
                assert path == "reference"

    def test_pallas_on_cpu_requires_explicit_interpret(self):
        if jax.default_backend() != "cpu":
            pytest.skip("CPU-only resolution rule")
        # plain "pallas" degrades to reference rather than silently
        # interpreting; explicit interpret=True opts in
        assert KernelConfig(sht="pallas").resolve("sht")[0] == "reference"
        assert KernelConfig(sht="pallas",
                            interpret=True).resolve("sht") == ("pallas", True)

    def test_compiled_backend_never_interprets(self, monkeypatch):
        # steer resolution to a compiled backend (TPU) from the test: a
        # "pallas" op there always compiles, and asking for the
        # interpreter is an error, not a slow path
        from repro.kernels import config as kconfig
        monkeypatch.setattr(kconfig, "compiled_backend", lambda: True)
        assert KernelConfig().resolve("disco") == ("pallas", False)
        assert KernelConfig(sht="pallas").resolve("sht") == ("pallas", False)
        assert KernelConfig(sht="reference").resolve("sht")[0] == "reference"
        with pytest.raises(ValueError, match="interpret"):
            KernelConfig(sht="pallas", interpret=True).resolve("sht")
        assert KernelConfig().effective() == {"sht": "pallas",
                                              "disco": "pallas"}

    def test_reference_mode_wins_everywhere(self):
        kc = KernelConfig(sht="reference", disco="reference", interpret=True)
        assert kc.resolve("sht")[0] == "reference"
        assert kc.resolve("disco")[0] == "reference"

    def test_validation(self):
        with pytest.raises(ValueError, match="sht"):
            KernelConfig(sht="cuda")
        with pytest.raises(ValueError, match="unknown kernel op"):
            KernelConfig().resolve("crps")

    def test_hashable_and_nestable(self):
        # nests inside FCN3Config/EngineConfig and cache keys
        assert hash(KernelConfig()) == hash(KernelConfig())
        assert KernelConfig() != PALLAS
        # blocks (empty by default) ride the tuple, so tuned configs
        # derive distinct engine/executable keys automatically
        assert dataclasses.astuple(PALLAS) == ("pallas", "pallas", True, ())


class TestSplitPsiBand:
    @pytest.mark.parametrize("gi,go", [
        ((64, 128, "equiangular"), (32, 64, "gauss")),
        ((33, 64, "equiangular"), (16, 32, "gauss")),
        ((16, 32, "gauss"), (16, 32, "gauss")),
        ((33, 64, "equiangular"), (33, 64, "equiangular")),
    ])
    def test_split_is_lossless_and_banded(self, gi, go):
        plan = dlib.make_disco_plan(grids.make_grid(*gi),
                                    grids.make_grid(*go))
        band, wrap_rows, psi_wrap = dlib.split_psi_band(plan.psi)
        k, h, s, w = plan.psi.shape
        d = band.shape[-1]
        assert d < w  # the band is a real band, not the full circle
        assert d % 2 == 1
        # reconstruct: wrap rows from psi_wrap, interior from the band
        recon = np.zeros_like(plan.psi)
        dh = d // 2
        idx = (np.arange(d) - dh) % w
        recon[:, :, :, idx] = band
        recon[:, wrap_rows] = psi_wrap
        np.testing.assert_array_equal(recon, plan.psi)

    def test_wrap_rows_cluster_at_the_poles(self):
        plan = dlib.make_disco_plan(grids.make_grid(64, 128, "equiangular"),
                                    grids.make_grid(32, 64, "gauss"))
        _, wrap_rows, _ = dlib.split_psi_band(plan.psi)
        h = plan.psi.shape[1]
        assert 0 < len(wrap_rows) < h // 2
        # every wrap row is in the first or last quarter of latitudes
        assert all(r < h // 4 or r >= h - h // 4 for r in wrap_rows)

    def test_d_max_moves_rows_to_wrap(self):
        plan = dlib.make_disco_plan(grids.make_grid(64, 128, "equiangular"),
                                    grids.make_grid(32, 64, "gauss"))
        band0, wrap0, _ = dlib.split_psi_band(plan.psi)
        band1, wrap1, _ = dlib.split_psi_band(plan.psi, d_max=5)
        assert band1.shape[-1] <= 5
        assert len(wrap1) >= len(wrap0)

    @pytest.mark.parametrize("gi,go", RUN_PLANS)
    def test_runs_cover_band_rows_once_in_order(self, gi, go):
        plan = dlib.make_disco_plan(grids.make_grid(*gi),
                                    grids.make_grid(*go))
        band, wrap_rows, _ = dlib.split_psi_band(plan.psi)
        runs = plan.band_runs
        h = plan.psi.shape[1]
        rows = [r for h0, h1, _ in runs for r in range(h0, h1)]
        assert rows == sorted(set(range(h)) - set(wrap_rows.tolist()))
        assert all(h0 < h1 and d % 2 == 1 and d <= band.shape[-1]
                   for h0, h1, d in runs)
        # maximal: two touching runs differ in their window
        w_blk = BLOCK_DEFAULTS["disco"]["w_blk"]
        for (_, e0, d0), (s1, _, d1) in zip(runs, runs[1:]):
            assert e0 < s1 or (disco_window(d0, plan.stride, w_blk)
                               != disco_window(d1, plan.stride, w_blk))
        assert 0 < plan.band_window_share <= 1

    @pytest.mark.parametrize("gi,go", RUN_PLANS)
    def test_run_taps_hold_every_nonzero_entry(self, gi, go):
        # structural and lossless, like the split: each run's centred
        # d taps plus the wrap rows rebuild psi exactly
        plan = dlib.make_disco_plan(grids.make_grid(*gi),
                                    grids.make_grid(*go))
        band, wrap_rows, psi_wrap = dlib.split_psi_band(plan.psi)
        w, dh = plan.psi.shape[-1], band.shape[-1] // 2
        recon = np.zeros_like(plan.psi)
        for h0, h1, d in plan.band_runs:
            taps = np.arange(dh - d // 2, dh + d // 2 + 1)
            recon[:, h0:h1][..., (taps - dh) % w] = band[:, h0:h1][..., taps]
        recon[:, wrap_rows] = psi_wrap
        np.testing.assert_array_equal(recon, plan.psi)

    def test_uniform_rows_make_one_run(self):
        plan = dlib.make_disco_plan(grids.make_grid(16, 32, "gauss"),
                                    grids.make_grid(16, 32, "gauss"))
        _, wrap_rows, _ = dlib.split_psi_band(plan.psi)
        assert plan.band_runs == ((3, 13, 11),)
        assert wrap_rows.tolist() == [0, 1, 2, 13, 14, 15]

    @settings(max_examples=15, deadline=None)
    @given(nlat=st.sampled_from([12, 16, 24]),
           d_max=st.integers(1, 64),
           cutoff=st.sampled_from([2.0, 3.0, 5.0]))
    def test_banded_psi_exact_flag_matches_support(self, nlat, d_max,
                                                   cutoff):
        # Satellite contract: exact=False whenever ANY nonzero psi entry
        # falls outside the band (e.g. pole-wrap rows truncated by
        # d_max), verified against a direct support computation.
        g = grids.make_grid(nlat, 2 * nlat, "equiangular")
        plan = dlib.make_disco_plan(g, g, cutoff_factor=cutoff)
        band, off0, exact = disco_ops.banded_psi_from_plan(plan,
                                                           d_max=d_max)
        w = plan.psi.shape[-1]
        d = band.shape[-1]
        inside = np.zeros(w, bool)
        inside[(np.arange(d) + off0) % w] = True
        outside_mass = np.any(plan.psi[:, :, :, ~inside])
        assert exact == (not outside_mass)


def _conv_params(key, c_in, c_out, n_basis, groups):
    return dlib.init_disco_conv(key, c_out, c_in, n_basis, groups=groups)


class TestDiscoDispatchParity:
    @pytest.mark.parametrize("gi,go", [
        ((64, 128, "equiangular"), (32, 64, "gauss")),   # encoder (stride 2)
        ((16, 32, "gauss"), (16, 32, "gauss")),          # latent block
        ((33, 64, "equiangular"), (33, 64, "equiangular")),  # decoder
    ])
    def test_banded_buffers_match_fft_path(self, gi, go):
        plan = dlib.make_disco_plan(grids.make_grid(*gi),
                                    grids.make_grid(*go))
        # (members, levels, channels): the levels fold into kernel rows
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, gi[0], gi[1]))
        params = _conv_params(jax.random.PRNGKey(1), 4, 6, plan.n_basis, 2)
        ref = dlib.apply_disco_conv(params, x, plan.buffers(), plan.stride,
                                    groups=2, affine=plan.affine)
        got = dlib.apply_disco_conv(params, x, plan.banded_buffers(),
                                    plan.stride, groups=2,
                                    affine=plan.affine, kernels=PALLAS,
                                    runs=plan.band_runs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    @pytest.mark.parametrize("gi,go", RUN_PLANS, ids=["stride1", "stride2"])
    def test_run_calls_match_fft_path(self, gi, go):
        plan = dlib.make_disco_plan(grids.make_grid(*gi),
                                    grids.make_grid(*go))
        assert len(plan.band_runs) >= 3
        assert plan.stride == gi[1] // go[1]
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 4, gi[0], gi[1]))
        params = _conv_params(jax.random.PRNGKey(4), 4, 6, plan.n_basis, 2)
        ref = dlib.apply_disco_conv(params, x, plan.buffers(), plan.stride,
                                    groups=2, affine=plan.affine)
        got = dlib.apply_disco_conv(params, x, plan.banded_buffers(),
                                    plan.stride, groups=2,
                                    affine=plan.affine, kernels=PALLAS,
                                    runs=plan.band_runs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_banded_dispatch_needs_the_runs(self):
        g = grids.make_grid(16, 32, "gauss")
        plan = dlib.make_disco_plan(g, g)
        x = jnp.zeros((1, 2, 16, 32))
        params = _conv_params(jax.random.PRNGKey(0), 2, 2, plan.n_basis, 1)
        with pytest.raises(ValueError, match="runs"):
            dlib.apply_disco_conv(params, x, plan.banded_buffers(),
                                  plan.stride, affine=plan.affine,
                                  kernels=PALLAS)
        with pytest.raises(ValueError, match="wrap rows"):
            dlib.apply_disco_conv(params, x, plan.banded_buffers(),
                                  plan.stride, affine=plan.affine,
                                  kernels=PALLAS, runs=((3, 12, 11),))

    def test_dispatch_follows_buffer_layout(self):
        g = grids.make_grid(16, 32, "gauss")
        plan = dlib.make_disco_plan(g, g)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 16, 32))
        params = _conv_params(jax.random.PRNGKey(2), 5, 3, plan.n_basis, 1)
        a = dlib.apply_disco_conv(params, x, plan.buffers(), plan.stride,
                                  affine=plan.affine)
        b = dlib.apply_disco_conv(params, x, plan.banded_buffers(),
                                  plan.stride, affine=plan.affine,
                                  kernels=PALLAS, runs=plan.band_runs)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


class TestSHTDispatchParity:
    def test_forward_inverse_match_reference(self):
        g = grids.make_grid(32, 64, "gauss")
        t = sht.SHT.create(g)
        bufs = t.buffers()
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 32, 64))
        np.testing.assert_allclose(
            np.asarray(kdispatch.sht_forward(x, bufs["wpct"], PALLAS)),
            np.asarray(t.forward(x)), atol=1e-5)
        c = t.forward(x)
        np.testing.assert_allclose(
            np.asarray(kdispatch.sht_inverse(c, bufs["pct"], 64, PALLAS)),
            np.asarray(t.inverse(c)), atol=1e-4)

    def test_reference_config_is_bitwise_reference(self):
        g = grids.make_grid(16, 32, "gauss")
        t = sht.SHT.create(g)
        bufs = t.buffers()
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
        np.testing.assert_array_equal(
            np.asarray(kdispatch.sht_forward(x, bufs["wpct"],
                                             KernelConfig())),
            np.asarray(t.forward(x)))


@pytest.fixture(scope="module")
def models():
    cfg_ref = cfgs.fcn3_smoke()
    cfg_pal = dataclasses.replace(cfg_ref, kernels=PALLAS)
    m_ref, m_pal = FCN3(cfg_ref), FCN3(cfg_pal)
    params = m_ref.init(jax.random.PRNGKey(0))
    return cfg_ref, m_ref, m_pal, params, m_ref.make_buffers(), \
        m_pal.make_buffers()


def _model_inputs(cfg, model, batch=1, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    state = jax.random.normal(k1, (batch, cfg.n_state, cfg.nlat, cfg.nlon))
    aux = jax.random.normal(k2, (batch, cfg.n_aux, cfg.nlat, cfg.nlon))
    z = model.sample_noise(k3, (batch,))
    return state, jnp.concatenate([aux, z], axis=1)


class TestFCN3PallasDispatch:
    def test_banded_buffers_never_materialize_full_psi(self, models):
        cfg, m_ref, m_pal, params, b_ref, b_pal = models
        for name, plan in (("enc", m_pal.enc_plan),
                           ("latent", m_pal.latent_plan),
                           ("dec", m_pal.dec_plan)):
            bufs = b_pal[name]
            k, h, s, w = plan.psi.shape
            assert "psi" not in bufs  # acceptance: no full (K,H,S,W) psi
            assert bufs["psi_band"].shape[-1] < w
            assert bufs["psi_band"].shape[:3] == (k, h, s)
            hw = bufs["wrap_rows"].shape[0]
            assert hw < h
            assert bufs["psi_wrap"].shape == (k, hw, s, w)
            # and the reference layout still carries the full psi
            assert b_ref[name]["psi"].shape == (k, h, s, w)

    def test_band_report_counts_each_plans_runs(self, models):
        cfg, m_ref, m_pal, params, b_ref, b_pal = models
        report = m_pal.disco_band_report()
        for name, plan in (("enc", m_pal.enc_plan),
                           ("latent", m_pal.latent_plan),
                           ("dec", m_pal.dec_plan)):
            assert report[name]["runs"] == len(plan.band_runs) >= 1
            assert 0 < report[name]["window_share"] <= 1

    def test_buffer_specs_mirror_buffers(self, models):
        cfg, m_ref, m_pal, params, b_ref, b_pal = models
        specs = m_pal.buffer_specs()
        flat_b = jax.tree.map(lambda a: (a.shape, a.dtype), b_pal)
        flat_s = jax.tree.map(lambda a: (a.shape, a.dtype), specs)
        assert flat_b == flat_s

    def test_apply_parity_fp32(self, models):
        cfg, m_ref, m_pal, params, b_ref, b_pal = models
        state, cond = _model_inputs(cfg, m_ref)
        out_ref = m_ref.apply(params, b_ref, state, cond)
        out_pal = m_pal.apply(params, b_pal, state, cond)
        np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_grad_parity_through_pallas(self, models):
        # custom-VJP backward passes (reference oracles) keep the model
        # trainable/calibratable under pallas dispatch
        cfg, m_ref, m_pal, params, b_ref, b_pal = models
        state, cond = _model_inputs(cfg, m_ref)
        g_ref = jax.grad(lambda p: m_ref.apply(p, b_ref, state,
                                               cond).sum())(params)
        g_pal = jax.grad(lambda p: m_pal.apply(p, b_pal, state,
                                               cond).sum())(params)
        for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                        jax.tree_util.tree_leaves(g_pal)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=2e-3, atol=2e-4)


class TestEnginePallasDispatch:
    @pytest.fixture(scope="class")
    def rollouts(self):
        from repro.data import era5_synthetic as dlib_data
        from repro.inference import EngineConfig, ForecastEngine
        cfg = cfgs.fcn3_smoke()
        model = FCN3(cfg)
        ds = dlib_data.SyntheticERA5(cfg)
        buffers = model.make_buffers()
        cond0 = jnp.concatenate(
            [jnp.asarray(ds.aux_fields(0.0))[None],
             model.sample_noise(jax.random.PRNGKey(1), (1,))], axis=1)
        params = model.init_calibrated(jax.random.PRNGKey(0),
                                       ds.state(0)[None], cond0, buffers)
        key = jax.random.PRNGKey(7)

        def run(ecfg):
            eng = ForecastEngine(model, ecfg)
            return eng, eng.forecast(
                params, buffers, ds.state(0),
                lambda n: ds.aux_fields(6.0 * (n + 1)), key, steps=3,
                truth=lambda n: ds.state(0, n + 1))

        base = EngineConfig(members=2, lead_chunk=2)
        _, ref = run(base)
        eng_pal, pal = run(dataclasses.replace(base, kernels=PALLAS))
        return eng_pal, ref, pal

    def test_forecast_rollout_parity(self, rollouts):
        # Acceptance criterion: full fp32 rollout, pallas dispatch
        # (interpret on CPU CI) vs reference, within 1e-4 rtol.
        _, ref, pal = rollouts
        np.testing.assert_allclose(np.asarray(pal.final_state),
                                   np.asarray(ref.final_state),
                                   rtol=1e-4, atol=1e-5)
        for name in ("crps", "ens_rmse", "spread"):
            np.testing.assert_allclose(np.asarray(pal.scores[name]),
                                       np.asarray(ref.scores[name]),
                                       rtol=1e-4, atol=1e-6, err_msg=name)

    def test_engine_adapts_caller_buffer_layout(self, rollouts):
        # the engine received reference-layout buffers (the serving
        # bundle's) and re-homed them on the banded layout internally
        eng_pal, _, _ = rollouts
        assert eng_pal.model.cfg.kernels == PALLAS
        _, prepared = eng_pal._prepare_inputs(
            None, FCN3(cfgs.fcn3_smoke()).make_buffers())
        assert "psi_band" in prepared["enc"]
        assert "psi" not in prepared["enc"]
