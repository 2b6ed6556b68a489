"""Benchmark harness -- one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Each benchmark is a reduced,
CPU-runnable analogue of a paper artifact; the full-scale numbers live in
EXPERIMENTS.md (dry-run roofline terms for the production mesh).

  fig3_crps / fig15_ssr / fig16_rank_hist -- probabilistic skill, calibration
  fig5_spectral_fidelity                  -- angular PSD ratio vs truth
  sec5_inference_speed                    -- autoregressive rollout step time
  sec5_serving                            -- served-request latency: cold vs
                                             warm executable cache, 1 vs N
                                             concurrent requests
  sec5_serving_qos                        -- pickup-policy A/B under overload:
                                             FIFO vs priority-then-FIFO with
                                             deadline shedding
  sec5_observability                      -- instrumentation cost A/B: warm
                                             request latency with tracing
                                             disabled vs enabled (overhead
                                             must sit within host noise)
  sec5_serving_faults                     -- fault-substrate cost A/B: warm
                                             request latency with injection
                                             unarmed (NULL_FAULTS) vs armed
                                             on a never-firing fault
  sec5_kernels                            -- op-level SHT/DISCO dispatch A/B
                                             (reference vs Pallas substrate)
                                             + banded-psi buffer footprint
  sec5_kernels_tuned                      -- autotuned vs default Pallas tile
                                             shapes per op (in-process sweep,
                                             achieved GFLOP/s + GB/s)
  table3_train_step                       -- ensemble CRPS train-step time
  kernel_*                                -- Pallas hot-spot kernels
  secG_dryrun_rooflines                   -- production-mesh roofline summary

``--json-out`` additionally writes every emitted row to a JSON artifact
(list of {name, us_per_call, derived}), which CI uploads.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, n=5, warmup=2, best=False) -> float:
    """Mean microseconds per call; ``best=True`` returns the fastest of n
    calls instead (a stable lower bound for noisy-host A/B comparisons)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return (min(ts) if best else sum(ts) / n) * 1e6  # us


def _ab_timeit(fns, n=10, warmup=2) -> list[float]:
    """Best-of-n microseconds per call for competing candidates, measured
    round-robin so slow host drift hits all candidates equally."""
    for fn in fns:
        for _ in range(warmup):
            jax.block_until_ready(fn())
    best = [float("inf")] * len(fns)
    for _ in range(n):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[i] = min(best[i], time.perf_counter() - t0)
    return [b * 1e6 for b in best]


#: rows emitted this run, for the ``--json-out`` artifact
ROWS: list[dict] = []


def _row(name: str, us: float, derived) -> None:
    ROWS.append({"name": name, "us_per_call": round(us, 1),
                 "derived": str(derived)})
    print(f"{name},{us:.1f},{derived}")


def _setup_model():
    from repro.configs import fcn3 as fcn3cfg
    from repro.core.fcn3 import FCN3
    from repro.data import era5_synthetic as dlib
    cfg = fcn3cfg.fcn3_smoke()
    model = FCN3(cfg)
    ds = dlib.SyntheticERA5(cfg)
    buffers = model.make_buffers()
    cond0 = jnp.concatenate(
        [jnp.asarray(ds.aux_fields(0.0))[None],
         model.sample_noise(jax.random.PRNGKey(1), (1,))], axis=1)
    params = model.init_calibrated(jax.random.PRNGKey(0),
                                   ds.state(0)[None], cond0, buffers)
    return cfg, model, ds, buffers, params


def bench_probabilistic_skill() -> None:
    """Fig. 3 / 12 / 13 / 15 / 16: CRPS, ens-mean RMSE, SSR, rank hist."""
    from repro.evaluation import metrics
    from repro.core.sphere import grids
    g = grids.make_grid(64, 128, "gauss")
    aw = jnp.asarray(g.area_weights_2d(), jnp.float32)
    key = jax.random.PRNGKey(0)
    ens = jax.random.normal(key, (16, 8, 64, 128))
    obs = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 128))

    crps_fn = jax.jit(lambda e, o: metrics.crps(e, o, aw).mean())
    us = _timeit(lambda: crps_fn(ens, obs))
    _row("fig3_crps", us, f"crps={float(crps_fn(ens, obs)):.4f}")

    ssr_fn = jax.jit(lambda e, o: metrics.spread_skill_ratio(e, o, aw).mean())
    us = _timeit(lambda: ssr_fn(ens, obs))
    _row("fig15_ssr", us, f"ssr={float(ssr_fn(ens, obs)):.3f}")

    rh_fn = jax.jit(lambda e, o: metrics.rank_histogram(e, o, aw))
    us = _timeit(lambda: rh_fn(ens, obs))
    h = np.asarray(rh_fn(ens, obs))
    _row("fig16_rank_hist", us, f"flatness={float(h.max() / h.min()):.3f}")


def bench_spectral_fidelity() -> None:
    """Fig. 5 / 23: angular PSD of a forecast member vs ERA5-like truth."""
    from repro.evaluation import metrics
    cfg, model, ds, buffers, params = _setup_model()
    wpct = model.in_sht.buffers()["wpct"]
    state = ds.state(3)
    cond = jnp.concatenate(
        [jnp.asarray(ds.aux_fields(6.0))[None],
         model.sample_noise(jax.random.PRNGKey(5), (1,))], axis=1)
    fwd = jax.jit(lambda s, c: model.apply(params, buffers, s, c))
    pred = fwd(state[None], cond)[0]
    psd_fn = jax.jit(lambda x: metrics.angular_psd(x, wpct))
    us = _timeit(lambda: psd_fn(pred[0]))
    p_pred = np.asarray(psd_fn(pred[0]))
    p_true = np.asarray(psd_fn(ds.state(3, 1)[0]))
    lo = slice(1, cfg.latent_nlat // 2)
    ratio = float(np.median(p_pred[lo] / np.maximum(p_true[lo], 1e-12)))
    _row("fig5_spectral_fidelity", us, f"psd_ratio={ratio:.3f}")


def bench_inference_speed(members: int = 2, steps: int = 8) -> None:
    """Section 5: ensemble autoregressive rollout, scan engine vs legacy
    per-step-dispatch loop, A/B in the same process (paper: 60-day 0.25-deg
    forecast in under 4 minutes on one GPU; here a reduced model on CPU).

    Rows report per-step microseconds for ``members``-member ensembles:
      * sec5_inference_speed          -- scan-compiled ForecastEngine
      * sec5_inference_speed_scored   -- engine incl. in-scan CRPS/RMSE/SSR
                                         and the rank histogram
      * sec5_inference_speed_calibrated -- scored + per-degree energy
                                         spectra (one extra SHT per member,
                                         channel and lead)
      * sec5_inference_speed_legacy   -- one jitted dispatch per lead time
    """
    from repro.core.sphere import noise as noiselib
    from repro.inference import EngineConfig, ForecastEngine
    cfg, model, ds, buffers, params = _setup_model()
    state0 = ds.state(0)
    key = jax.random.PRNGKey(7)
    aux = jnp.stack([jnp.asarray(ds.aux_fields(6.0 * (k + 1)))
                     for k in range(steps)])
    truth = jnp.stack([ds.state(0, k + 1) for k in range(steps)])
    steps_15d = 60  # 15 days at 6-hourly

    # -- legacy baseline: jitted step (state + noise transition) built
    #    once, dispatched from Python per lead time, as in
    #    `repro.launch.serve --legacy-loop`.
    nbufs = model.noise.buffers()

    @jax.jit
    def step_fn(params, s, z_hat, aux_n, n):
        z = model.noise.to_grid(z_hat, nbufs)
        z = noiselib.center_noise(z, axis=0)
        cond = jnp.concatenate(
            [jnp.broadcast_to(aux_n, (members,) + aux_n.shape), z], axis=1)
        s = jax.vmap(lambda se, ce: model.apply(params, buffers, se, ce)
                     )(s, cond)
        return s, model.noise.step(jax.random.fold_in(key, n), z_hat, nbufs)

    def run_legacy():
        z_hat = model.noise.init_state(key, (members,), nbufs)
        s = jnp.broadcast_to(state0, (members,) + state0.shape)
        for n in range(steps):
            s, z_hat = step_fn(params, s, z_hat, aux[n], n)
        return s

    # static_buffers: the legacy step closes over the geometry too, so
    # this is the like-for-like single-host comparison.
    eng = ForecastEngine(model, EngineConfig(members=members,
                                             lead_chunk=steps,
                                             static_buffers=True))
    # Same engine with per-degree energy spectra added to the in-scan
    # score set: the A/B isolates the calibration-scoring overhead.
    eng_cal = ForecastEngine(model, EngineConfig(members=members,
                                                 lead_chunk=steps,
                                                 static_buffers=True,
                                                 spectra=True))

    def run_engine(e=eng, truth_arr=None):
        return e.forecast(params, buffers, state0, aux, key,
                          truth=truth_arr).final_state

    # Interleaved best-of timing: host noise on shared CPU runners is
    # ~10%, far above the dispatch-overhead difference being measured, and
    # drifts over seconds -- so alternate the candidates round-robin and
    # take each one's fastest round.
    us_eng, us_leg, us_sco, us_cal = (
        u / steps for u in _ab_timeit(
            [run_engine, run_legacy,
             lambda: run_engine(truth_arr=truth),
             lambda: run_engine(e=eng_cal, truth_arr=truth)], n=30))
    _row("sec5_inference_speed", us_eng,
         f"members={members};steps={steps};"
         f"legacy_us={us_leg:.1f};speedup={us_leg / us_eng:.2f}x;"
         f"15day_forecast_s={us_eng * steps_15d / 1e6:.2f}")
    _row("sec5_inference_speed_scored", us_sco,
         f"scoring_overhead={us_sco / us_eng:.2f}x")
    _row("sec5_inference_speed_calibrated", us_cal,
         f"calibration_overhead={us_cal / us_sco:.2f}x_vs_scored")
    _row("sec5_inference_speed_legacy", us_leg,
         f"15day_forecast_s={us_leg * steps_15d / 1e6:.2f}")


def bench_serving(members: int = 2, steps: int = 4) -> None:
    """Section 5, served: request latency/throughput through the serving
    scheduler (queue -> executable cache -> chunk-streamed rollout).

    Rows (microseconds per request):
      * sec5_serving_cold_request -- first request for a shape key: pays
        lower+compile once (``compile_s`` in the derived column)
      * sec5_serving_warm_request -- same shape again: cache hit, zero
        compile, the cold-vs-warm ratio is the executable cache's win
      * sec5_serving_throughput_n{1,4,8} -- aggregate throughput A/B: N
        concurrent same-shape requests through a coalescing scheduler
        (one batched rollout) vs a serial one (N rollouts back to
        back); both warm, so the derived requests/sec and wall-clock
        ratio isolate the coalescing win
    """
    from repro.serving.cache import ExecutableCache
    from repro.serving.scheduler import (ForecastScheduler, ModelPool,
                                         RequestSpec)
    pool = ModelPool()
    sched = ForecastScheduler(pool=pool, cache=ExecutableCache(),
                              max_concurrency=2)
    spec = RequestSpec(config="smoke", members=members, lead_steps=steps,
                       lead_chunk=max(1, steps // 2), scored=True)

    def burst(s, n) -> float:
        """Wall-clock seconds to serve n concurrent same-shape requests
        (distinct samples/seeds, as real traffic would be)."""
        t0 = time.perf_counter()
        streams = [s.submit(RequestSpec(**{**spec.to_dict(),
                                           "sample": i, "seed": i}))
                   for i in range(n)]
        for st in streams:
            st.result()
        return time.perf_counter() - t0

    try:
        t0 = time.perf_counter()
        cold = sched.submit(spec).result()
        cold_s = time.perf_counter() - t0
        _row("sec5_serving_cold_request", cold_s * 1e6,
             f"compile_s={cold.timing['compile_s']:.2f};"
             f"setup_s={cold.timing['setup_s']:.2f};"
             f"cache_misses={cold.cache['misses']}")

        t0 = time.perf_counter()
        warm = sched.submit(spec).result()
        warm_s = time.perf_counter() - t0
        assert warm.timing["compile_s"] == 0.0, "warm request recompiled"
        _row("sec5_serving_warm_request", warm_s * 1e6,
             f"compile_s={warm.timing['compile_s']:.2f};"
             f"cache_misses={warm.cache['misses']};"
             f"cold_vs_warm={cold_s / warm_s:.1f}x")

        # Aggregate throughput: coalesced vs serial, both fully warm.
        # One coalescing scheduler per n with max_batch=n (the operator
        # tunes max_batch to the traffic; a full batch closes without
        # spending the window), and best-of-3 round-robin bursts -- the
        # same noisy-host discipline as _ab_timeit.
        for n in (1, 4, 8):
            # one worker: a second would race the burst and split it
            # into smaller (unwarmed) batches, making the formed-batch
            # histogram -- and the timed region -- nondeterministic
            coal = ForecastScheduler(pool=pool, cache=ExecutableCache(),
                                     max_concurrency=1, max_batch=n,
                                     batch_window_ms=250.0)
            try:
                coal.warmup(spec, batch=n if n > 1 else None)
                serial_s = coal_s = float("inf")
                for _ in range(3):
                    serial_s = min(serial_s, burst(sched, n))
                    coal_s = min(coal_s, burst(coal, n))
                batches = coal.stats()["batches"]
                _row(f"sec5_serving_throughput_n{n}", coal_s / n * 1e6,
                     f"n={n};coalesced_rps={n / coal_s:.2f};"
                     f"serial_rps={n / serial_s:.2f};"
                     f"coalesced_wall_s={coal_s:.3f};"
                     f"serial_wall_s={serial_s:.3f};"
                     f"speedup={serial_s / coal_s:.2f}x;"
                     f"batches="
                     + "+".join(f"{k}x{v}"
                                for k, v in sorted(batches.items())))
            finally:
                coal.close()
    finally:
        sched.close()


def bench_serving_qos(members: int = 2, steps: int = 4) -> None:
    """docs/serving.md QoS section: pickup-policy A/B under overload.

    One warm single-worker scheduler per arm, same 9-request burst (6
    batch then 3 interactive -- a human arriving behind a sweep):
      * FIFO arm  -- ``aging_ms=0`` promotes everything, restoring pure
        FIFO pickup (the QoS fields ride along but cannot reorder);
      * QoS arm   -- priority-then-FIFO: interactive requests jump the
        batch backlog; two extra already-expired requests prove the
        deadline shed path (terminal error, zero rollouts burned).

    The row's value is the QoS arm's mean interactive total_s; derived
    carries per-arm interactive p95 queue_s and the shed count.
    """
    from repro.serving import transport
    from repro.serving.cache import ExecutableCache
    from repro.serving.scheduler import (ForecastScheduler, ModelPool,
                                         RequestSpec)
    pool = ModelPool()
    spec = RequestSpec(config="smoke", members=members, lead_steps=steps,
                       lead_chunk=max(1, steps // 2), scored=True)

    def burst(s, with_shed: bool) -> dict:
        streams = []
        for i in range(6):
            streams.append(("batch", s.submit(RequestSpec(
                **{**spec.to_dict(), "sample": i, "seed": i}))))
        shed_streams = []
        if with_shed:
            for i in range(2):
                shed_streams.append(s.submit(RequestSpec(
                    **{**spec.to_dict(), "seed": 50 + i,
                       "deadline_ms": 0.001})))
        for i in range(3):
            streams.append(("interactive", s.submit(RequestSpec(
                **{**spec.to_dict(), "sample": i, "seed": 20 + i,
                   "priority": "interactive"}))))
        out = {"batch": [], "interactive": []}
        for cls, st in streams:
            res = st.result()
            out[cls].append((res.timing["queue_s"],
                             res.timing["total_s"]))
        shed = 0
        for st in shed_streams:
            try:
                st.result()
            except transport.ServingError as e:
                assert e.reason == "deadline", e
                shed += 1
        out["shed"] = shed
        return out

    arms = {}
    for name, aging_ms in (("fifo", 0.0), ("qos", 60000.0)):
        sched = ForecastScheduler(pool=pool, cache=ExecutableCache(),
                                  max_concurrency=1, aging_ms=aging_ms)
        try:
            sched.warmup(spec)
            arms[name] = burst(sched, with_shed=(name == "qos"))
            stats = sched.stats()
            # shed requests never reached a worker: every dispatched
            # rollout is accounted to a served request
            assert sum(int(k) * v
                       for k, v in stats["batches"].items()) == \
                stats["served"], stats
            arms[name]["stats"] = stats
        finally:
            sched.close()

    def p95(samples, idx):
        return float(np.percentile([s[idx] for s in samples], 95))

    qos_int = arms["qos"]["interactive"]
    fifo_q, qos_q = (p95(arms[a]["interactive"], 0)
                     for a in ("fifo", "qos"))
    mean_total = sum(t for _, t in qos_int) / len(qos_int)
    _row("sec5_serving_qos", mean_total * 1e6,
         f"fifo_interactive_p95_queue_s={fifo_q:.3f};"
         f"qos_interactive_p95_queue_s={qos_q:.3f};"
         f"speedup={fifo_q / max(qos_q, 1e-9):.1f}x;"
         f"qos_batch_p95_queue_s={p95(arms['qos']['batch'], 0):.3f};"
         f"shed={arms['qos']['shed']}")


def bench_train_step() -> None:
    """Table 3: one ensemble-CRPS training step (stage-1 recipe, reduced)."""
    from repro.configs import fcn3 as fcn3cfg
    from repro.data import era5_synthetic as dlib
    from repro.train import trainer as trlib
    cfg, model, ds, buffers, params = _setup_model()
    tcfg = trlib.TrainConfig(ensemble_size=2, rollout_steps=1)
    tr = trlib.EnsembleTrainer(model, tcfg,
                               fcn3cfg.channel_weights(cfg.n_levels))
    opt_state = tr.optimizer.init(params)
    batch = next(iter(dlib.Loader(ds, global_batch=1, rollout=1)))
    step = jax.jit(tr.make_train_step(buffers))
    p, o = params, opt_state

    def run():
        nonlocal p, o
        p, o, aux = step(p, o, batch, jax.random.PRNGKey(0))
        return aux["loss"]

    us = _timeit(run, n=3, warmup=1)
    _row("table3_train_step", us, f"samples_per_s={1e6 / us:.2f}")


def bench_kernels() -> None:
    """Pallas kernels vs pure-jnp oracles (interpret mode on CPU)."""
    from repro.kernels.legendre.legendre import legendre_contract
    from repro.kernels.legendre.ref import legendre_contract_ref
    from repro.kernels.crps.crps import crps_fused
    from repro.kernels.crps.ref import crps_fused_ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 128, 16)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(16, 128, 128)), jnp.float32)
    us_k = _timeit(lambda: legendre_contract(x, t), n=3)
    ref = jax.jit(legendre_contract_ref)
    us_r = _timeit(lambda: ref(x, t), n=3)
    _row("kernel_legendre_interp", us_k, f"ref_us={us_r:.1f}")

    ens = jnp.asarray(rng.normal(size=(16, 65536)), jnp.float32)
    obs = jnp.asarray(rng.normal(size=(65536,)), jnp.float32)
    us_k = _timeit(lambda: crps_fused(ens, obs, fair=True), n=3)
    refc = jax.jit(lambda e, o: crps_fused_ref(e, o, fair=True))
    us_r = _timeit(lambda: refc(ens, obs), n=3)
    _row("kernel_crps_interp", us_k, f"ref_us={us_r:.1f}")


def bench_sec5_kernels() -> None:
    """Section 5 / App. B.5, C: op-level kernel-substrate A/B.

    Times the two hot contractions of the FCN3 step -- the SHT (forward
    and inverse) and the DISCO convolution -- through the reference
    XLA path vs the Pallas dispatch (interpret mode on CPU, compiled on
    TPU/GPU; the ``mode`` field in the derived column says which ran),
    and reports the static-memory win of the banded psi split vs the
    full (K, H, S, W) tensor.
    """
    from repro.core.sphere import disco as dlib
    from repro.core.sphere import grids, sht
    from repro.kernels import autotune, dispatch as kdispatch
    from repro.kernels.config import KernelConfig, default_interpret

    interpret = default_interpret()
    mode = "interpret" if interpret else "compiled"
    kc = KernelConfig(sht="pallas", disco="pallas", interpret=interpret)
    # the baseline must pin "reference" explicitly: a bare dispatch call
    # resolves "auto" to pallas on TPU/GPU and would A/B pallas vs itself
    rc = KernelConfig(sht="reference", disco="reference")

    # SHT at the smoke model's latent resolution, batched over channels.
    g = grids.make_grid(32, 64, "gauss")
    t = sht.SHT.create(g)
    bufs = t.buffers()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32, 64))
    fwd_ref = jax.jit(lambda x: kdispatch.sht_forward(x, bufs["wpct"], rc))
    fwd_pal = jax.jit(lambda x: kdispatch.sht_forward(x, bufs["wpct"], kc))
    # every derived row names the mode that ran and the Pallas tile spec
    # (the defaults here; sec5_kernels_tuned A/Bs the swept winners)
    leg_blocks = autotune.format_blocks("legendre")
    dis_blocks = autotune.format_blocks("disco")
    us_r, us_p = _ab_timeit([lambda: fwd_ref(x), lambda: fwd_pal(x)], n=5)
    _row("sec5_kernels_sht_forward", us_p,
         f"ref_us={us_r:.1f};mode={mode};blocks={leg_blocks};"
         f"speedup={us_r / us_p:.2f}x")

    c = fwd_ref(x)
    inv_ref = jax.jit(lambda c: kdispatch.sht_inverse(c, bufs["pct"], 64,
                                                      rc))
    inv_pal = jax.jit(lambda c: kdispatch.sht_inverse(c, bufs["pct"], 64, kc))
    us_r, us_p = _ab_timeit([lambda: inv_ref(c), lambda: inv_pal(c)], n=5)
    _row("sec5_kernels_sht_inverse", us_p,
         f"ref_us={us_r:.1f};mode={mode};blocks={leg_blocks};"
         f"speedup={us_r / us_p:.2f}x")

    # DISCO conv (contraction + channel mix) on a real encoder plan
    # (equiangular -> Gaussian downsampling).
    gi = grids.make_grid(64, 128, "equiangular")
    go = grids.make_grid(32, 64, "gauss")
    plan = dlib.make_disco_plan(gi, go)
    full = plan.buffers(jnp.float32)
    band = plan.banded_buffers(jnp.float32)
    xd = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 128))
    wd = dlib.init_disco_conv(jax.random.PRNGKey(2), 8, 8, plan.n_basis)
    dis_ref = jax.jit(lambda x: dlib.apply_disco_conv(
        wd, x, full, plan.stride, affine=plan.affine))
    dis_pal = jax.jit(lambda x: dlib.apply_disco_conv(
        wd, x, band, plan.stride, affine=plan.affine, kernels=kc,
        runs=plan.band_runs))
    us_r, us_p = _ab_timeit([lambda: dis_ref(xd), lambda: dis_pal(xd)], n=5)
    _row("sec5_kernels_disco", us_p,
         f"ref_us={us_r:.1f};mode={mode};blocks={dis_blocks};"
         f"speedup={us_r / us_p:.2f}x")

    # Static-memory footprint: banded split vs full psi, both for the
    # benchmark plan and extrapolated to the paper's 721x1440 encoder.
    full_b = full["psi"].size * 4
    band_b = (band["psi_band"].size + band["psi_wrap"].size) * 4
    _row("sec5_kernels_psi_bytes", 0.0,
         f"full_bytes={full_b};band_bytes={band_b};"
         f"ratio={full_b / max(band_b, 1):.1f}x;"
         f"wrap_rows={int(band['wrap_rows'].shape[0])}/{plan.psi.shape[1]};"
         f"mode={mode};blocks={dis_blocks}")


def bench_sec5_kernels_tuned() -> None:
    """Autotuner A/B: default vs swept Pallas tile shapes, per op.

    Runs a real in-process sweep (``repro.kernels.autotune.sweep_op``
    into a throwaway ``TuningCache``) at the same op shapes
    ``sec5_kernels`` benchmarks, then reports one row per op with the
    winner's time as the value and a derived column carrying the default
    time, both tile specs, and the achieved GFLOP/s / HBM GB/s of the
    winner (``roofline_report.achieved`` over
    ``autotune.op_flops_bytes`` -- the same roofline arithmetic as the
    dry-run tables).  The default tile is always in the sweep, so
    ``speedup >= 1.0`` by construction.
    """
    import shutil
    import tempfile
    try:
        from roofline_report import achieved  # python benchmarks/run.py
    except ImportError:
        from benchmarks.roofline_report import achieved  # -m / pytest
    from repro.core.sphere import disco as dlib
    from repro.core.sphere import grids, sht
    from repro.kernels import autotune
    from repro.kernels.config import default_interpret

    interpret = default_interpret()
    mode = "interpret" if interpret else "compiled"

    # The exact problem shapes sec5_kernels times (so the two benchmark
    # families A/B the same work): the smoke-latent SHT slab, the
    # encoder-plan DISCO band and the kernel_crps_interp score slab.
    t = sht.SHT.create(grids.make_grid(32, 64, "gauss"))
    m, h, l = t.buffers()["wpct"].shape
    plan = dlib.make_disco_plan(grids.make_grid(64, 128, "equiangular"),
                                grids.make_grid(32, 64, "gauss"))
    k, h_out, s, d = plan.banded_buffers(jnp.float32)["psi_band"].shape
    ops_shapes = {
        "legendre": (16, h, l, m),
        "disco": (1, 8, 64, h_out, s, 128, k, d, 8, plan.stride),
        "crps": (16, 65536),
    }

    tmp = tempfile.mkdtemp(prefix="fcn3-bench-tune-")
    try:
        cache = autotune.TuningCache(tmp)
        for op, shapes in ops_shapes.items():
            entry = autotune.sweep_op(op, shapes, cache=cache,
                                      interpret=interpret,
                                      max_candidates=6, iters=3)
            best_s = entry["best_us"] * 1e-6
            flops, mem = autotune.op_flops_bytes(op, shapes)
            ach = achieved(flops, mem, best_s)
            speedup = entry["default_us"] / max(entry["best_us"], 1e-9)
            _row(f"sec5_kernels_tuned_{op}", entry["best_us"],
                 f"default_us={entry['default_us']:.1f};mode={mode};"
                 f"blocks={autotune.format_blocks(op, entry['dims'])};"
                 f"default_blocks={autotune.format_blocks(op)};"
                 f"speedup={speedup:.2f}x;"
                 f"gflops={ach['gflops']:.3f};gbs={ach['gbs']:.3f};"
                 f"candidates={len(entry['candidates'])};"
                 f"swept={int(entry['swept'])}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_dist_roofline() -> None:
    """Appendix G: reads the dry-run results if present and reports the
    roofline bottleneck histogram of the production-mesh baselines,
    scan-trip-corrected through ``benchmarks.roofline_report`` (one
    correction implementation, not two drifting copies)."""
    import json
    import os
    try:
        from roofline_report import corrected  # python benchmarks/run.py
    except ImportError:
        from benchmarks.roofline_report import corrected  # -m / pytest
    path = os.path.join(os.path.dirname(__file__), "..",
                        "dryrun_results.jsonl")
    if not os.path.exists(path):
        _row("secG_dryrun_rooflines", 0.0, "dryrun_results.jsonl missing")
        return
    t0 = time.perf_counter()
    rows = [corrected(json.loads(line)) for line in open(path)]
    us = (time.perf_counter() - t0) * 1e6
    single = [r for r in rows if r["mesh"] == "16x16"]
    from collections import Counter
    c = Counter(r["bottleneck"] for r in single)
    _row("secG_dryrun_rooflines", us,
         f"cases={len(single)} bottlenecks={dict(c)}".replace(",", ";"))


def bench_bundle(members: int = 2, steps: int = 4) -> None:
    """docs/deployment.md: replica cold boot vs warm-start-bundle boot.

    Rows (microseconds, boot-to-first-forecast):
      * sec5_bundle_cold_boot -- fresh scheduler with cleared geometry
        caches and an empty XLA cache: full plan build + trace + compile
        + first request (what every replica pays without a bundle)
      * sec5_bundle_warm_boot -- ``boot_scheduler`` over a packed bundle
        with the same caches cleared: verify + install plans + import
        StableHLO blobs, then the first request.  Zero compiles, proven
        by the engine's jit dispatch counter staying 0.
    """
    import os
    import shutil
    import tempfile
    from repro.core.sphere import disco as discolib
    from repro.core.sphere import legendre as leg
    from repro.serving.bundle import boot_scheduler, pack
    from repro.serving.cache import ExecutableCache
    from repro.serving.scheduler import (ForecastScheduler, ModelPool,
                                         RequestSpec)
    spec = RequestSpec(config="smoke", members=members, lead_steps=steps,
                       lead_chunk=max(1, steps // 2), scored=True)

    def clear_geometry_caches() -> None:
        discolib._cached_plan.cache_clear()
        discolib._PLAN_OVERRIDES.clear()
        leg._cached_table.cache_clear()
        leg._TABLE_OVERRIDES.clear()

    tmp = tempfile.mkdtemp(prefix="fcn3-bench-bundle-")
    try:
        bundle_path = pack([spec], out=os.path.join(tmp, "bundle"))

        # cold boot: nothing warm anywhere -- the full pipeline runs,
        # with the persistent compilation cache off so XLA compiles
        clear_geometry_caches()
        jax.config.update("jax_enable_compilation_cache", False)
        t0 = time.perf_counter()
        cold_sched = ForecastScheduler(pool=ModelPool(),
                                       cache=ExecutableCache(),
                                       max_concurrency=1)
        try:
            res = cold_sched.submit(spec).result()
            cold_s = time.perf_counter() - t0
            _row("sec5_bundle_cold_boot", cold_s * 1e6,
                 f"compile_s={res.timing['compile_s']:.2f};"
                 f"setup_s={res.timing['setup_s']:.2f}")
        finally:
            cold_sched.close()
            jax.config.update("jax_enable_compilation_cache", True)

        # bundle boot: same cleared caches, everything from the bundle
        clear_geometry_caches()
        t0 = time.perf_counter()
        sched = boot_scheduler(bundle_path, max_concurrency=1)
        try:
            res = sched.submit(spec).result()
            warm_s = time.perf_counter() - t0
            assert res.timing["compile_s"] == 0.0, "bundle boot compiled"
            eng = sched._engines.snapshot()[spec.engine_key()]
            assert eng.dispatch_counts["jit"] == 0, "bundle boot jitted"
            info = sched.bundle_info
            _row("sec5_bundle_warm_boot", warm_s * 1e6,
                 f"boot_s={info['boot_s']};"
                 f"disk_hits={info['disk_hits']};"
                 f"programs={info['programs']};"
                 f"cold_vs_bundle={cold_s / warm_s:.1f}x")
        finally:
            sched.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_observability(members: int = 2, steps: int = 4) -> None:
    """docs/observability.md: the instrumentation layer's cost A/B.

    One warm single-worker scheduler per arm serving the same request
    shape: tracing+flight recording *disabled*
    (``ObservabilityConfig(enabled=False)``, the structurally
    pre-instrumentation dispatch path) vs *enabled* (span tree + flight
    events recorded per request).  Round-robin best-of bursts, same
    noisy-host discipline as ``_ab_timeit``.  The row's value is the
    enabled arm's warm-request latency; ``overhead_pct`` in the derived
    column is the acceptance gate (must sit within host noise).
    """
    from repro.serving.cache import ExecutableCache
    from repro.serving.observability import ObservabilityConfig
    from repro.serving.scheduler import (ForecastScheduler, ModelPool,
                                         RequestSpec)
    pool = ModelPool()
    spec = RequestSpec(config="smoke", members=members, lead_steps=steps,
                       lead_chunk=max(1, steps // 2), scored=True)
    arms = {}
    try:
        for name, enabled in (("disabled", False), ("enabled", True)):
            arms[name] = ForecastScheduler(
                pool=pool, cache=ExecutableCache(), max_concurrency=1,
                observability=ObservabilityConfig(enabled=enabled))
            arms[name].warmup(spec)
            arms[name].submit(spec).result()  # first-request one-offs
        best = dict.fromkeys(arms, float("inf"))
        for _ in range(5):
            for name, sched in arms.items():
                t0 = time.perf_counter()
                sched.submit(spec).result()
                best[name] = min(best[name], time.perf_counter() - t0)
        overhead = 100.0 * (best["enabled"] - best["disabled"]) \
            / best["disabled"]
        traced = arms["enabled"].debug_requests()
        _row("sec5_observability", best["enabled"] * 1e6,
             f"enabled_us={best['enabled'] * 1e6:.1f};"
             f"disabled_us={best['disabled'] * 1e6:.1f};"
             f"overhead_pct={overhead:.2f};"
             f"flight_entries={len(traced['finished'])}")
    finally:
        for sched in arms.values():
            sched.close()


def bench_serving_faults(members: int = 2, steps: int = 4) -> None:
    """docs/serving.md#fault-tolerance: the fault substrate's cost A/B.

    One warm single-worker scheduler per arm serving the same request
    shape: *disabled* (no ``--fault`` args, the scheduler holds
    ``NULL_FAULTS`` and the dispatch path is structurally identical to
    pre-fault-tolerance) vs *armed-but-idle* (a real injector armed on
    a fault that never fires, which additionally wraps H2D staging
    callables).  Round-robin best-of bursts, same noisy-host discipline
    as ``_ab_timeit``.  The row's value is the armed arm's warm-request
    latency; ``overhead_pct`` is the acceptance gate (the armed path
    exists for tests/chaos drills, but must still sit within host
    noise -- the *disabled* path's only cost is one ``is NULL_FAULTS``
    identity check).
    """
    from repro.serving.cache import ExecutableCache
    from repro.serving.faults import FaultInjector
    from repro.serving.scheduler import (ForecastScheduler, ModelPool,
                                         RequestSpec)
    pool = ModelPool()
    spec = RequestSpec(config="smoke", members=members, lead_steps=steps,
                       lead_chunk=max(1, steps // 2), scored=True)
    arms = {}
    try:
        for name, faults in (
                ("disabled", None),
                ("armed_idle", FaultInjector.from_args(
                    ["rollout_chunk:n=1000000000"]))):
            arms[name] = ForecastScheduler(
                pool=pool, cache=ExecutableCache(), max_concurrency=1,
                faults=faults)
            arms[name].warmup(spec)
            arms[name].submit(spec).result()  # first-request one-offs
        best = dict.fromkeys(arms, float("inf"))
        for _ in range(5):
            for name, sched in arms.items():
                t0 = time.perf_counter()
                sched.submit(spec).result()
                best[name] = min(best[name], time.perf_counter() - t0)
        overhead = 100.0 * (best["armed_idle"] - best["disabled"]) \
            / best["disabled"]
        fired = arms["armed_idle"].stats()["fault_tolerance"][
            "faults"]["fired"]
        assert not fired, f"idle arm fired faults: {fired}"
        _row("sec5_serving_faults", best["armed_idle"] * 1e6,
             f"armed_idle_us={best['armed_idle'] * 1e6:.1f};"
             f"disabled_us={best['disabled'] * 1e6:.1f};"
             f"overhead_pct={overhead:.2f}")
    finally:
        for sched in arms.values():
            sched.close()


def _append_history(path: str, rows: list[dict]) -> None:
    """Append this run's sec5 rows to a benchmark-trajectory JSON file.

    Each appended entry is a row plus provenance (git SHA, UTC date,
    jax backend), so CI runs accumulate a queryable latency/throughput
    history across commits (the ``BENCH_serving.json`` artifact).

    The trajectory doubles as a regression guard: a new row whose
    ``us_per_call`` exceeds the last recorded entry for the same
    (name, backend) by more than 10% prints a ``REGRESSION?`` warning to
    stderr.  A warning, not a failure -- shared CI hosts are noisy and
    the history carries the evidence either way.
    """
    import datetime
    import os
    import subprocess
    import sys
    sha = os.environ.get("GITHUB_SHA")
    if not sha:
        try:
            sha = subprocess.run(["git", "rev-parse", "HEAD"],
                                 capture_output=True, text=True,
                                 check=True).stdout.strip()
        except (OSError, subprocess.CalledProcessError):
            sha = "unknown"
    stamp = {"sha": sha[:12],
             "date": datetime.datetime.now(datetime.timezone.utc)
             .strftime("%Y-%m-%dT%H:%M:%SZ"),
             "backend": jax.default_backend()}
    try:
        with open(path) as f:
            history = json.load(f)
        if not isinstance(history, list):
            raise ValueError(f"{path} is not a JSON list")
    except FileNotFoundError:
        history = []
    last = {}  # (name, backend) -> most recent us_per_call on record
    for old in history:
        if isinstance(old, dict) and "name" in old:
            last[(old["name"], old.get("backend"))] = old.get("us_per_call")
    for row in rows:
        if not row["name"].startswith("sec5"):
            continue
        prev = last.get((row["name"], stamp["backend"]))
        if prev and row["us_per_call"] > 1.1 * prev:
            print(f"REGRESSION? {row['name']} us_per_call="
                  f"{row['us_per_call']:.1f} vs last {prev:.1f} "
                  f"(+{100 * (row['us_per_call'] / prev - 1):.0f}%, "
                  f"backend={stamp['backend']})", file=sys.stderr)
    history.extend({**stamp, **row} for row in rows
                   if row["name"].startswith("sec5"))
    with open(path, "w") as f:
        json.dump(history, f, indent=2)


BENCHES = {
    "fig3_probabilistic_skill": lambda a: bench_probabilistic_skill(),
    "fig5_spectral_fidelity": lambda a: bench_spectral_fidelity(),
    "sec5_inference_speed": lambda a: bench_inference_speed(a.members,
                                                            a.steps),
    "sec5_serving": lambda a: bench_serving(a.members, a.steps),
    "sec5_serving_qos": lambda a: bench_serving_qos(a.members, a.steps),
    "sec5_observability": lambda a: bench_observability(a.members, a.steps),
    "sec5_serving_faults": lambda a: bench_serving_faults(a.members,
                                                          a.steps),
    "sec5_bundle": lambda a: bench_bundle(a.members, a.steps),
    "sec5_kernels": lambda a: bench_sec5_kernels(),
    "sec5_kernels_tuned": lambda a: bench_sec5_kernels_tuned(),
    "table3_train_step": lambda a: bench_train_step(),
    "kernel_pallas": lambda a: bench_kernels(),
    "secG_dryrun_rooflines": lambda a: bench_dist_roofline(),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="run only benchmarks whose name contains this "
                         "substring (e.g. sec5_inference_speed)")
    ap.add_argument("--members", type=int, default=2,
                    help="ensemble size for sec5_inference_speed")
    ap.add_argument("--steps", type=int, default=8,
                    help="lead steps for sec5_inference_speed (short "
                         "rollouts under-amortize the engine's one-off "
                         "per-forecast setup)")
    ap.add_argument("--json-out", default=None,
                    help="also write the emitted rows to this JSON file "
                         "(the CI benchmark artifact)")
    ap.add_argument("--history", default=None, metavar="PATH",
                    help="append this run's sec5 rows (plus git SHA, UTC "
                         "date and jax backend) to a benchmark-trajectory "
                         "JSON list, e.g. BENCH_serving.json")
    args = ap.parse_args(argv)
    selected = {n: fn for n, fn in BENCHES.items()
                if args.only is None or args.only in n}
    if not selected:
        raise SystemExit(f"no benchmark matches --only {args.only!r}")
    from repro import compile_cache
    compile_cache.configure()
    print("name,us_per_call,derived")
    for fn in selected.values():
        fn(args)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"backend": jax.default_backend(), "rows": ROWS}, f,
                      indent=2)
    if args.history:
        _append_history(args.history, ROWS)


if __name__ == "__main__":
    main()
