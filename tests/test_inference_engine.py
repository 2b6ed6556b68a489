"""Tests for the scan-compiled ensemble inference engine (paper 5/G.4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import fcn3 as fcn3cfg
from repro.core.fcn3 import FCN3
from repro.data import era5_synthetic as dlib
from repro.evaluation import metrics
from repro.inference import EngineConfig, ForecastEngine
from repro.launch import serve

MEMBERS, STEPS, SAMPLE = 4, 3, 11
KEY = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def setup():
    cfg = fcn3cfg.fcn3_smoke()
    model = FCN3(cfg)
    ds = dlib.SyntheticERA5(cfg)
    buffers = model.make_buffers()
    state0 = ds.state(SAMPLE, 0)
    cond0 = jnp.concatenate(
        [jnp.asarray(ds.aux_fields(0.0))[None],
         model.sample_noise(jax.random.PRNGKey(1), (1,))], axis=1)
    params = model.init_calibrated(jax.random.PRNGKey(0), state0[None],
                                   cond0, buffers)
    return cfg, model, ds, buffers, params, state0


def _aux_fn(ds):
    return lambda n: ds.aux_fields(6.0 * (n + 1))


def _legacy_final(model, params, buffers, state0, ds):
    ens = None
    for _, s in serve.legacy_forecast(model, params, buffers, state0,
                                      _aux_fn(ds), KEY, MEMBERS, STEPS):
        ens = s
    return np.asarray(ens)


class TestScanMatchesLegacy:
    @pytest.mark.parametrize("lead_chunk", [STEPS, 2])
    def test_bit_for_bit_fp32(self, setup, lead_chunk):
        # (a) one compiled scan == per-step-dispatch loop, bitwise, incl.
        # an uneven final chunk (lead_chunk=2 over 3 steps).
        cfg, model, ds, buffers, params, state0 = setup
        legacy = _legacy_final(model, params, buffers, state0, ds)
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=lead_chunk))
        res = eng.forecast(params, buffers, state0, _aux_fn(ds), KEY,
                           steps=STEPS)
        assert res.final_state.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(res.final_state), legacy)

    def test_static_buffers_match_argument_buffers(self, setup):
        # Baked-constant geometry is an executable-layout optimization
        # only; it must not change a single bit.
        cfg, model, ds, buffers, params, state0 = setup
        outs = []
        for static in (False, True):
            eng = ForecastEngine(model, EngineConfig(
                members=MEMBERS, lead_chunk=2, static_buffers=static))
            outs.append(np.asarray(eng.forecast(
                params, buffers, state0, _aux_fn(ds), KEY,
                steps=STEPS).final_state))
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_in_scan_scores_match_host_metrics(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=STEPS))
        res = eng.forecast(params, buffers, state0, _aux_fn(ds), KEY,
                           steps=STEPS,
                           truth=lambda n: ds.state(SAMPLE, n + 1))
        aw = jnp.asarray(ds.grid.area_weights_2d(), jnp.float32)
        truth = ds.state(SAMPLE, STEPS)
        ens = res.final_state
        np.testing.assert_allclose(
            np.asarray(res.scores["crps"][-1]),
            np.asarray(metrics.crps(ens, truth, aw)), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(res.scores["ens_rmse"][-1]),
            np.asarray(metrics.ensemble_skill(ens, truth, aw)), rtol=1e-5)
        assert res.scores["ssr"].shape == (STEPS, cfg.n_state)


class TestDonation:
    def test_repeat_forecasts_identical(self, setup):
        # (b) donated state/noise carries must not leak between calls.
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2, donate=True))

        def run():
            return np.asarray(eng.forecast(params, buffers, state0,
                                           _aux_fn(ds), KEY,
                                           steps=STEPS).final_state)

        first, second = run(), run()
        np.testing.assert_array_equal(first, second)

    def test_sequential_members_match_vmapped(self, setup, monkeypatch):
        # Full-width models step members one after another (memory);
        # forcing that path at smoke size must reproduce the vmapped
        # step up to float reassociation.
        from repro.inference import engine as englib
        cfg, model, ds, buffers, params, state0 = setup
        outs = []
        for threshold in (englib.SEQUENTIAL_MEMBER_BYTES, 0):
            monkeypatch.setattr(englib, "SEQUENTIAL_MEMBER_BYTES", threshold)
            eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                     lead_chunk=2))
            assert eng._members_in_sequence == (threshold == 0)
            outs.append(np.asarray(eng.forecast(
                params, buffers, state0, _aux_fn(ds), KEY,
                steps=STEPS).final_state))
        np.testing.assert_allclose(outs[1], outs[0], rtol=1e-5, atol=1e-5)

    def test_donation_off_matches_on(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        outs = []
        for donate in (True, False):
            eng = ForecastEngine(model, EngineConfig(
                members=MEMBERS, lead_chunk=2, donate=donate))
            outs.append(np.asarray(eng.forecast(
                params, buffers, state0, _aux_fn(ds), KEY,
                steps=STEPS).final_state))
        np.testing.assert_array_equal(outs[0], outs[1])


class TestNoiseCentering:
    def test_antithetic_pairs_at_step0(self, setup):
        # (c) paper E.3: odd members see the negated noise of their even
        # partner, exactly as the scan body consumes it.
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 centered=True))
        _, z_hat = eng.init_carry(state0, KEY)
        z = np.asarray(eng.noise_fields(z_hat))
        assert z.shape == (MEMBERS, cfg.n_noise, cfg.nlat, cfg.nlon)
        np.testing.assert_array_equal(z[1::2], -z[0::2])
        assert np.abs(z[0::2]).max() > 0  # non-degenerate noise

    def test_uncentered_members_independent(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 centered=False))
        _, z_hat = eng.init_carry(state0, KEY)
        z = np.asarray(eng.noise_fields(z_hat))
        assert np.abs(z[1] + z[0]).max() > 1e-6  # not antithetic


class TestPrecisionPolicy:
    def test_bf16_compute_fp32_scores(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(
            members=MEMBERS, lead_chunk=STEPS, compute_dtype="bfloat16"))
        res = eng.forecast(params, buffers, state0, _aux_fn(ds), KEY,
                           steps=STEPS,
                           truth=lambda n: ds.state(SAMPLE, n + 1))
        assert res.final_state.dtype == jnp.bfloat16
        for v in res.scores.values():
            assert v.dtype == jnp.float32
            assert bool(jnp.isfinite(v).all())
        # bf16 rollout stays close to the fp32 trajectory on 3 steps
        ref = ForecastEngine(model, EngineConfig(
            members=MEMBERS, lead_chunk=STEPS)).forecast(
                params, buffers, state0, _aux_fn(ds), KEY, steps=STEPS)
        err = np.abs(np.asarray(res.final_state, np.float32)
                     - np.asarray(ref.final_state))
        assert err.max() < 0.15


class TestStreamChunkBoundaries:
    """Chunking is an execution detail: any lead_chunk, any aux/truth
    staging style, scored or not, must reproduce the single-chunk
    rollout bit-for-bit (the serving layer relies on this when it picks
    chunk sizes for latency, not numerics)."""

    STEPS = 5  # lead_chunk=2 leaves an uneven final chunk [4]
    _engines: dict = {}  # engines reused across tests (compile once)

    def _run(self, setup, lead_chunk, scored, as_arrays):
        cfg, model, ds, buffers, params, state0 = setup
        eng = self._engines.get(lead_chunk)
        if eng is None:
            eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                     lead_chunk=lead_chunk))
            self._engines[lead_chunk] = eng
        aux = _aux_fn(ds)
        truth = (lambda n: ds.state(SAMPLE, n + 1)) if scored else None
        if as_arrays:
            aux = jnp.stack([jnp.asarray(aux(n))
                             for n in range(self.STEPS)])
            if scored:
                truth = jnp.stack([ds.state(SAMPLE, n + 1)
                                   for n in range(self.STEPS)])
        return eng.forecast(params, buffers, state0, aux, KEY,
                            steps=self.STEPS, truth=truth)

    @pytest.mark.parametrize("scored", [True, False])
    def test_uneven_final_chunk_matches_unchunked(self, setup, scored):
        ref = self._run(setup, self.STEPS, scored, as_arrays=False)
        res = self._run(setup, 2, scored, as_arrays=False)
        np.testing.assert_array_equal(np.asarray(res.final_state),
                                      np.asarray(ref.final_state))
        assert set(res.scores) == set(ref.scores)
        for name in ref.scores:
            np.testing.assert_array_equal(np.asarray(res.scores[name]),
                                          np.asarray(ref.scores[name]),
                                          err_msg=name)

    def test_callable_vs_array_staging_identical(self, setup):
        ref = self._run(setup, 2, True, as_arrays=False)
        res = self._run(setup, 2, True, as_arrays=True)
        np.testing.assert_array_equal(np.asarray(res.final_state),
                                      np.asarray(ref.final_state))
        np.testing.assert_array_equal(np.asarray(res.scores["crps"]),
                                      np.asarray(ref.scores["crps"]))

    def test_chunk_lengths_enumerates_dispatches(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        assert eng.chunk_lengths(5) == [2, 1]
        assert eng.chunk_lengths(4) == [2]
        assert eng.chunk_lengths(1) == [1]
        eng2 = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                  lead_chunk=8))
        assert eng2.chunk_lengths(3) == [3]


class TestAOTHooks:
    def test_compiled_chunks_dispatch_and_match_jit(self, setup):
        # compile_chunk installs executables; the rollout must dispatch
        # them exclusively and stay bit-identical to the implicit-jit
        # engine.
        cfg, model, ds, buffers, params, state0 = setup
        ref_eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                     lead_chunk=2))
        ref = ref_eng.forecast(params, buffers, state0, _aux_fn(ds), KEY,
                               steps=STEPS,
                               truth=lambda n: ds.state(SAMPLE, n + 1))
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        for k in eng.chunk_lengths(STEPS):
            eng.compile_chunk(True, k, params, buffers)
            assert eng.has_chunk_executable(True, k, params, buffers)
        res = eng.forecast(params, buffers, state0, _aux_fn(ds), KEY,
                           steps=STEPS,
                           truth=lambda n: ds.state(SAMPLE, n + 1))
        assert eng.dispatch_counts["aot"] == 2
        assert eng.dispatch_counts["jit"] == 0
        np.testing.assert_array_equal(np.asarray(res.final_state),
                                      np.asarray(ref.final_state))
        for name in ref.scores:
            np.testing.assert_array_equal(np.asarray(res.scores[name]),
                                          np.asarray(ref.scores[name]),
                                          err_msg=name)

    def test_different_params_falls_back_to_jit(self, setup):
        # AOT executables are pinned to the params object they were
        # compiled against; a different object must not crash -- it
        # falls back to the (retracing) jit path.
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=STEPS))
        eng.compile_chunk(False, STEPS, params, buffers)
        other = jax.tree.map(lambda a: a + 0, params)
        assert not eng.has_chunk_executable(False, STEPS, other, buffers)
        res = eng.forecast(other, buffers, state0, _aux_fn(ds), KEY,
                           steps=STEPS)
        assert eng.dispatch_counts["jit"] == 1
        assert bool(jnp.isfinite(res.final_state).all())

    def test_lower_chunk_exposes_staged_compile(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        lowered = eng.lower_chunk(True, 2, params, buffers)
        assert isinstance(lowered, jax.stages.Lowered)
        assert hasattr(lowered.compile(), "__call__")


class TestBatchedRollout:
    """Coalesced-request batching: B same-shape requests through one
    vmapped chunk program must be bit-identical, per request, to B
    serial rollouts (the serving scheduler's coalescing relies on
    this being a pure throughput move)."""

    SAMPLES = (11, 3, 5, 2)
    SEEDS = (7, 9, 1, 4)

    def _serial(self, setup, eng, sm, sd, scored=True):
        cfg, model, ds, buffers, params, state0 = setup
        return eng.forecast(params, buffers, ds.state(sm, 0), _aux_fn(ds),
                            jax.random.PRNGKey(sd), steps=STEPS,
                            truth=(lambda n: ds.state(sm, n + 1))
                            if scored else None)

    def _batched(self, setup, eng, scored=True):
        cfg, model, ds, buffers, params, state0 = setup
        return eng.forecast_batched(
            params, buffers, [ds.state(sm, 0) for sm in self.SAMPLES],
            [_aux_fn(ds) for _ in self.SAMPLES],
            [jax.random.PRNGKey(sd) for sd in self.SEEDS], steps=STEPS,
            truths=[(lambda sm=sm: lambda n: ds.state(sm, n + 1))()
                    for sm in self.SAMPLES] if scored else None)

    def test_batched_bit_identical_to_serial(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        refs = [self._serial(setup, eng, sm, sd)
                for sm, sd in zip(self.SAMPLES, self.SEEDS)]
        results = self._batched(setup, eng)
        assert len(results) == len(self.SAMPLES)
        for res, ref in zip(results, refs):
            np.testing.assert_array_equal(np.asarray(res.final_state),
                                          np.asarray(ref.final_state))
            np.testing.assert_array_equal(res.lead_steps, ref.lead_steps)
            assert set(res.scores) == set(ref.scores)
            for name in ref.scores:
                np.testing.assert_array_equal(
                    np.asarray(res.scores[name]),
                    np.asarray(ref.scores[name]), err_msg=name)

    def test_batched_perturbed_members_match_serial(self, setup):
        # perturbed member init runs per request inside the batched
        # path, so obs-error members stay bitwise equal to serial too
        from repro.inference import PerturbationConfig
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(
            members=MEMBERS, lead_chunk=2,
            perturb=PerturbationConfig(kind="obs", amplitude=0.05)))
        refs = [self._serial(setup, eng, sm, sd)
                for sm, sd in zip(self.SAMPLES[:2], self.SEEDS[:2])]
        results = eng.forecast_batched(
            params, buffers, [ds.state(sm, 0) for sm in self.SAMPLES[:2]],
            [_aux_fn(ds) for _ in range(2)],
            [jax.random.PRNGKey(sd) for sd in self.SEEDS[:2]], steps=STEPS,
            truths=[(lambda sm=sm: lambda n: ds.state(sm, n + 1))()
                    for sm in self.SAMPLES[:2]])
        for res, ref in zip(results, refs):
            np.testing.assert_array_equal(np.asarray(res.final_state),
                                          np.asarray(ref.final_state))
            np.testing.assert_array_equal(np.asarray(res.scores["crps"]),
                                          np.asarray(ref.scores["crps"]))

    def test_batched_aot_executables_dispatch(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        b = len(self.SAMPLES)
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        for k in eng.chunk_lengths(STEPS):
            eng.compile_chunk(True, k, params, buffers, batch=b)
            assert eng.has_chunk_executable(True, k, params, buffers,
                                            batch=b)
        # the serial programs are NOT installed: batch is its own key
        assert not eng.has_chunk_executable(True, 2, params, buffers)
        self._batched(setup, eng)
        assert eng.dispatch_counts["aot"] == 2
        assert eng.dispatch_counts["jit"] == 0

    def test_batched_input_length_mismatch_rejected(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        with pytest.raises(ValueError, match="one entry per request"):
            list(eng.stream_batched(params, buffers,
                                    [state0, state0], [_aux_fn(ds)],
                                    [KEY, KEY], steps=STEPS))


class TestHostStaging:
    """The chunk stager must stage every (request, step) exactly once
    per rollout (no re-materialized jnp.asarray chunks) while
    prefetching chunk k+1 during chunk k."""

    def test_each_step_staged_exactly_once(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        calls: list[int] = []

        def aux(n):
            calls.append(n)
            return ds.aux_fields(6.0 * (n + 1))

        eng.forecast(params, buffers, state0, aux, KEY, steps=STEPS)
        assert sorted(calls) == list(range(STEPS))  # once per step
        d = eng.dispatch_stats()
        assert d["h2d_chunks"] == 2  # chunks [0,1] and [2]
        assert d["h2d_steps"] == STEPS

    def test_bred_init_reuses_first_chunk(self, setup):
        # bred-vector init needs step 0's aux before the rollout; it
        # must come from the already-staged first chunk, not a second
        # H2D copy of step 0
        from repro.inference import PerturbationConfig
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(
            members=2, lead_chunk=2,
            perturb=PerturbationConfig(kind="bred", bred_cycles=1)))
        calls: list[int] = []

        def aux(n):
            calls.append(n)
            return ds.aux_fields(6.0 * (n + 1))

        eng.forecast(params, buffers, state0, aux, KEY, steps=STEPS)
        assert sorted(calls) == list(range(STEPS))
        assert eng.dispatch_stats()["h2d_steps"] == STEPS

    def test_batched_staging_counts_distinct_sources(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        eng.forecast_batched(params, buffers, [state0, state0],
                             [_aux_fn(ds), _aux_fn(ds)],
                             [KEY, jax.random.PRNGKey(3)], steps=STEPS)
        d = eng.dispatch_stats()
        assert d["h2d_chunks"] == 2
        assert d["h2d_steps"] == 2 * STEPS  # 2 distinct sources x 3 steps

    def test_batched_staging_dedupes_shared_sources(self, setup):
        # the scheduler hands every coalesced member the same aux
        # callable: one staging for the whole batch, not B identical
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        calls: list[int] = []

        def aux(n):
            calls.append(n)
            return ds.aux_fields(6.0 * (n + 1))

        eng.forecast_batched(params, buffers, [state0, state0],
                             [aux, aux], [KEY, jax.random.PRNGKey(3)],
                             steps=STEPS)
        assert sorted(calls) == list(range(STEPS))  # staged once, shared
        assert eng.dispatch_stats()["h2d_steps"] == STEPS


class TestStreaming:
    def test_stream_chunks_concat_to_forecast(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(model, EngineConfig(members=MEMBERS,
                                                 lead_chunk=2))
        blocks = list(eng.stream(params, buffers, state0, _aux_fn(ds), KEY,
                                 steps=STEPS,
                                 truth=lambda n: ds.state(SAMPLE, n + 1)))
        assert [b.lead_steps.tolist() for b in blocks] == [[0, 1], [2]]
        assert blocks[0].final_state is None  # carry donated onward
        assert blocks[-1].final_state is not None
        whole = eng.forecast(params, buffers, state0, _aux_fn(ds), KEY,
                             steps=STEPS,
                             truth=lambda n: ds.state(SAMPLE, n + 1))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(b.scores["crps"]) for b in blocks]),
            np.asarray(whole.scores["crps"]))

    def test_diagnostics_traced_into_scan(self, setup):
        cfg, model, ds, buffers, params, state0 = setup
        eng = ForecastEngine(
            model, EngineConfig(members=MEMBERS, lead_chunk=2),
            diagnostics=lambda ens: {"absmax": jnp.abs(ens).max(axis=(1, 2, 3))})
        res = eng.forecast(params, buffers, state0, _aux_fn(ds), KEY,
                           steps=STEPS)
        assert res.diagnostics["absmax"].shape == (STEPS, MEMBERS)
        assert bool(jnp.isfinite(res.diagnostics["absmax"]).all())
