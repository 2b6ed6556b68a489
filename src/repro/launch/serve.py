"""Ensemble-forecast serving on the compiled inference engine (paper §5/G.4).

Generates an N-member FCN3 ensemble forecast and scores it (CRPS /
ensemble-mean RMSE / spread-skill) *in situ*, never writing raw fields to
disk -- the paper's distributed online-inference design.

The default path is ``repro.inference.ForecastEngine``: the full rollout
(FCN3 step, AR(1) spherical-noise transition, antithetic centering,
metric accumulation) runs inside chunked ``jax.lax.scan`` calls that are
compiled once, with donated ensemble-state/noise carries.  Engine knobs
exposed here:

* ``--lead-chunk K``   scan length per compiled chunk (compile time /
                       memory vs dispatch-count trade-off);
* ``--precision bfloat16``  bf16 model compute with fp32 metric
                       accumulation;
* ``--perturb {none,obs,bred}``  on-device initial-condition
                       perturbations (paper App. E): obs-error sampling
                       or cycled bred vectors, antithetically centered,
                       scaled by the dataset's climatological stats;
* ``--calibration``    per-degree energy spectra in the scan and a
                       calibration summary (rank-histogram flatness,
                       spread-skill, spectral ratio) per lead time --
                       see docs/calibration.md;
* ``--scores-out F``   save every in-scan score array to ``F`` (.npz);
* members shard over the ``member_axes`` mesh convention of
  ``train.trainer`` when the engine is constructed with one (this CLI
  runs the single-host default).

``--legacy-loop`` keeps the original per-step-dispatch Python loop for
A/B timing; both paths are bit-identical in fp32.

  PYTHONPATH=src python -m repro.launch.serve --config smoke \
      --members 4 --lead-steps 8 --perturb obs --calibration
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import fcn3 as fcn3cfg
from repro.core.fcn3 import FCN3
from repro.core.sphere import noise as noiselib
from repro.data import era5_synthetic as dlib
from repro.evaluation import metrics
from repro.inference import (EngineConfig, ForecastEngine,
                             InitialConditionPerturbation,
                             PerturbationConfig)
from repro.inference import perturbations as perturblib
from repro.inference.params import load_params

CONFIGS = fcn3cfg.NAMED_CONFIGS


def legacy_forecast(model: FCN3, params, buffers, state0, aux_fn, key,
                    members: int, steps: int, centered: bool = True):
    """Per-step-dispatch rollout: yields (step, ensemble_state).

    Kept as the A/B baseline for the scan engine.  One jitted step per
    lead time (state + noise transition fused in a single dispatch);
    aux fields are staged from host every step.
    """
    nbufs = model.noise.buffers()
    z_hat = model.noise.init_state(key, (members,), nbufs)
    s = jnp.broadcast_to(state0, (members,) + state0.shape)

    @jax.jit
    def step_fn(params, s, z_hat, aux, n):
        z = model.noise.to_grid(z_hat, nbufs)
        if centered:
            z = noiselib.center_noise(z, axis=0)
        cond = jnp.concatenate(
            [jnp.broadcast_to(aux, (members,) + aux.shape), z], axis=1)
        s = jax.vmap(lambda se, ce: model.apply(params, buffers, se, ce)
                     )(s, cond)
        z_hat = model.noise.step(jax.random.fold_in(key, n), z_hat, nbufs)
        return s, z_hat

    for n in range(steps):
        aux = jnp.asarray(aux_fn(n))
        s, z_hat = step_fn(params, s, z_hat, aux, n)
        yield n, s


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="smoke", choices=sorted(CONFIGS))
    ap.add_argument("--members", type=int, default=4)
    ap.add_argument("--lead-steps", type=int, default=8)
    ap.add_argument("--lead-chunk", type=int, default=8,
                    help="scan steps per compiled chunk (engine path)")
    ap.add_argument("--precision", default="float32",
                    choices=["float32", "bfloat16"],
                    help="model compute dtype; metrics stay fp32")
    ap.add_argument("--kernels", default="auto",
                    choices=["auto", "reference", "pallas"],
                    help="kernel substrate for the SHT/DISCO hot path "
                         "(auto: Pallas on TPU/GPU, reference on CPU); "
                         "engine path only")
    ap.add_argument("--legacy-loop", action="store_true",
                    help="per-step-dispatch baseline instead of the "
                         "scan-compiled engine")
    ap.add_argument("--perturb", default="none",
                    choices=["none", "obs", "bred"],
                    help="on-device initial-condition perturbation of the "
                         "members (engine path)")
    ap.add_argument("--perturb-amplitude", type=float, default=0.05,
                    help="perturbation size as a fraction of the "
                         "climatological channel std")
    ap.add_argument("--bred-cycles", type=int, default=3,
                    help="breeding cycles for --perturb bred")
    ap.add_argument("--ensemble-transform", action="store_true",
                    help="orthogonalize bred-vector pairs against each "
                         "other every cycle (ensemble-transform "
                         "rescaling) instead of only renormalizing")
    ap.add_argument("--calibration", action="store_true",
                    help="in-scan per-degree energy spectra + calibration "
                         "summary per lead (rank-histogram flatness, "
                         "spectral ratio)")
    ap.add_argument("--scores-out", default=None,
                    help="save all in-scan score arrays to this .npz file")
    ap.add_argument("--sample", type=int, default=123)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.configure()
    if args.legacy_loop and (args.perturb != "none" or args.calibration
                             or args.scores_out or args.kernels != "auto"):
        ap.error("--perturb/--calibration/--scores-out/--kernels require "
                 "the engine path")
    # Validate member/perturbation combinations before any tracing: both
    # paths antithetically center the conditioning noise, so an odd
    # member count silently un-centers the ensemble mean.
    try:
        pcfg = PerturbationConfig(kind=args.perturb,
                                  amplitude=args.perturb_amplitude,
                                  bred_cycles=args.bred_cycles,
                                  ensemble_transform=args.ensemble_transform)
    except ValueError as e:
        ap.error(str(e))
    problems = perturblib.validate_member_count(args.members, centered=True,
                                                cfg=pcfg)
    if problems:
        ap.error("; ".join(problems))

    cfg = CONFIGS[args.config]()
    model = FCN3(cfg)
    ds = dlib.SyntheticERA5(cfg)
    buffers = model.make_buffers()
    state0 = ds.state(args.sample, 0)
    params = load_params(model, ds, buffers, state0, args.ckpt)

    key = jax.random.PRNGKey(7)
    aw = jnp.asarray(ds.grid.area_weights_2d(), jnp.float32)
    t0 = time.time()
    mode = "legacy per-step loop" if args.legacy_loop else (
        f"scan engine (chunk={args.lead_chunk}, {args.precision})")
    print(f"[serve] {args.members}-member ensemble, "
          f"{args.lead_steps} x 6h lead -- {mode}")

    def report(n, crps, skill, ssr):
        print(f"lead {6 * (n + 1):4d}h  CRPS={crps:.4f} "
              f"ensRMSE={skill:.4f} SSR={ssr:.3f} "
              f"({time.time() - t0:.1f}s)")

    if args.legacy_loop:
        for n, ens in legacy_forecast(model, params, buffers, state0,
                                      lambda k: ds.aux_fields(6.0 * (k + 1)),
                                      key, args.members, args.lead_steps):
            truth = ds.state(args.sample, n + 1)
            report(n, float(metrics.crps(ens, truth, aw).mean()),
                   float(metrics.ensemble_skill(ens, truth, aw).mean()),
                   float(metrics.spread_skill_ratio(ens, truth, aw).mean()))
    else:
        # Single-host CLI: bake the geometry into the executable except at
        # full resolution, where the Legendre tables are GB-scale and must
        # stay jit arguments (shardable, not HLO constants).
        perturbation = (InitialConditionPerturbation.from_dataset(
            model.in_sht, pcfg, ds) if pcfg.active else None)
        from repro.kernels.config import KernelConfig
        kernels = (None if args.kernels == "auto"
                   else KernelConfig(sht=args.kernels, disco=args.kernels))
        eng = ForecastEngine(model, EngineConfig(
            members=args.members, lead_chunk=args.lead_chunk,
            compute_dtype=args.precision,
            static_buffers=args.config != "full",
            perturb=pcfg, spectra=args.calibration,
            kernels=kernels),
            perturbation=perturbation)
        collected: dict[str, list] = {}
        for block in eng.stream(params, buffers, state0,
                                lambda n: ds.aux_fields(6.0 * (n + 1)), key,
                                steps=args.lead_steps,
                                truth=lambda n: ds.state(args.sample, n + 1)):
            if args.scores_out:
                # host copies only when they will be written: a long
                # rollout otherwise accumulates every (T, C, L) spectrum
                # on the host just to discard it
                for name, arr in block.scores.items():
                    collected.setdefault(name, []).append(np.asarray(arr))
            for i, n in enumerate(block.lead_steps):
                report(int(n), float(block.scores["crps"][i].mean()),
                       float(block.scores["ens_rmse"][i].mean()),
                       float(block.scores["ssr"][i].mean()))
                if args.calibration:
                    # Channel-mean rank histogram flatness (max/min bin
                    # frequency; 1 = perfectly flat) and the median
                    # forecast/truth spectral-power ratio (1 = neither
                    # blurred nor blown up) -- docs/calibration.md.
                    rh = np.asarray(block.scores["rank_hist"][i]).mean(0)
                    spec = np.asarray(block.scores["spectrum"][i])
                    spec_t = np.asarray(block.scores["spectrum_truth"][i])
                    lo = spec.shape[-1] // 2
                    ratio = np.median(spec[:, 1:lo]
                                      / np.maximum(spec_t[:, 1:lo], 1e-12))
                    print(f"          rank-hist flatness="
                          f"{rh.max() / max(rh.min(), 1e-12):.2f} "
                          f"spectral ratio={ratio:.3f}")
        if args.scores_out:
            scores = {k: np.concatenate(v) for k, v in collected.items()}
            np.savez(args.scores_out, **scores)
            print(f"[serve] scores -> {args.scores_out} "
                  f"({', '.join(sorted(scores))})")
    print("[serve] done -- no fields written to disk (in-situ scoring)")


if __name__ == "__main__":
    main()
