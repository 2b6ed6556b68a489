#!/usr/bin/env python3
"""Serve full-width FCN3 on one TPU through the real service, and check it.

    python chip_smoke.py              # one chip: the served path at 721x1440
    python chip_smoke.py --chips 4    # member sharding over a 4-chip mesh

One chip.  Starts the forecast service with the launcher's own start-up
code (``repro.launch.service.build_service``: preload ``full`` -- the
paper's 721x1440 grid, 13 levels, 10 blocks, seeded calibrated weights,
``SyntheticERA5`` state --, warm the request shape, scheduler, engine,
AOT executable cache), listens on a localhost port and answers a cold and
a warm request through ``repro.serving.client`` over HTTP/NDJSON, in this
one process.  Then it steps the same params and initial state once
through the Pallas dispatch and once through the float32 reference
dispatch and compares them.

Four chips (``--chips 4``, and nothing else).  Rolls one ensemble with
its members sharded over a 4-device mesh (``EngineConfig.member_axes``)
and compares it with the same rollout on device 0 alone.

Any failed phase, or a first device that is not a TPU, exits non-zero.
On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The compile cache follows ``repro.compile_cache`` (``JAX_COMPILATION_CACHE_DIR``
when set).  Flags for the TPU runtime go into ``LIBTPU_INIT_ARGS`` by
appending to what the machine sets, never by replacing it.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: the request every served phase uses: two members (fair CRPS needs
#: two), four 6-hour steps dispatched one compiled step at a time, scored
#: in-scan.  One step per chunk: a scan over several steps lets XLA hoist
#: rounded copies of the loop-invariant weights out of it (2 members at
#: full width: temp 6.0 GB at one step per chunk, 8.0 GB at two --
#: compile rehearsal), and the host stager's next chunk and the data
#: source's truth synthesis need room beside the program.
SPEC = {"config": "full", "members": 2, "lead_steps": 4, "lead_chunk": 1,
        "scored": True}

#: Pallas-vs-reference one-step bound on the per-channel relative RMS
#: error of the decoder output (before the water-channel softclamp).
#: The served step runs every matmul at the TPU's default precision (bf16
#: multiplier passes: relative rounding 2^-9 ~ 2e-3 per product) while
#: the reference runs float32 "highest" with FFT DISCO correlations; ~25
#: chained contractions of random-sign products add such errors to
#: ~1e-2.  A wrong kernel -- a misplaced tap, a roll in the wrong
#: direction, a lost wrap row or channel tile -- is O(1).  The bound is
#: taken before the softclamp (C.8) because the clamp is the same
#: pointwise map on both sides with slope <= 1, so it adds no error, but
#: it turns a water channel whose output lies mostly below zero into a
#: field of near-zeros whose RMS is no scale for an error.
PARITY_RTOL = 3e-2

#: member-sharded vs one-device rollout bound (relative, per score and
#: on the final state).  Both rollouts run at float32 ("highest"): the
#: same per-member math in two programs that XLA fuses differently
#: (float32 reassociation, ~1e-6 per step), compounded over the
#: rollout's steps.  At the TPU's default precision the two programs'
#: different dot shapes round their bf16 passes differently (5.3e-3 on
#: a v5e over 4 steps), which would hide a sharding fault of that size.
SHARD_RTOL = 1e-3

#: a run that has not finished by now has hung: dump every thread's stack
#: and exit non-zero, inside the 1200 s a smoke run may take
WATCHDOG_S = 1140


class SmokeFailure(RuntimeError):
    """A phase of the smoke run failed; the message says which check."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(dev: dict, msg: str) -> None:
    print(f"[{dev['platform']}:{dev['kind']} x{dev['count']}] {msg}",
          flush=True)


def find_tpu(count: int) -> dict:
    """The attached TPU as JAX reports it; refuses anything else."""
    import jax
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"first JAX device is {d.platform!r} ({d.device_kind}), not a "
          f"TPU: this smoke measures the chip and has no CPU fallback")
    check(len(devs) >= count,
          f"--chips {count} needs {count} devices, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peak_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


def finite_scores(res, label: str) -> None:
    import numpy as np
    for name in ("crps", "ens_rmse", "ssr"):
        check(name in res.scores, f"{label}: no {name!r} score")
        vals = np.asarray(res.scores[name])
        check(vals.size > 0 and bool(np.isfinite(vals).all()),
              f"{label}: {name} has non-finite values")


# ---------------------------------------------------------------------------
# One chip: the served path at full width
# ---------------------------------------------------------------------------

def serve_phase(dev: dict):
    """Start the real service at ``full``, answer a cold and a warm
    request over HTTP, check events, scores, compile counts and the
    kernel dispatch.  Returns the model bundle for the parity phase."""
    import jax

    from repro.launch import service as launcher
    from repro.serving import transport
    from repro.serving.client import ForecastClient

    argv = ["--config", "full", "--port", "0", "--warm", json.dumps(SPEC),
            "--log-level", "WARNING"]
    args = launcher.build_parser().parse_args(argv)
    t0 = time.perf_counter()
    service, startup = launcher.build_service(args)
    startup_s = time.perf_counter() - t0
    pre = startup["preload"]["full"]
    warm = startup["warm"][0]["compile_s"]
    say(dev, f"startup {startup_s:.1f} s: host plans {pre['plans_s']:.1f} s,"
             f" calibration {pre['calibrate_s']:.1f} s, warm compile "
             f"{warm:.1f} s; members {SPEC['members']}; peak "
             f"{peak_gb(jax.devices()[0])}")

    server = service.make_server("127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        client = ForecastClient(port=server.server_address[1],
                                read_timeout=600.0)
        for label, seed in (("cold", 7), ("warm", 8)):
            t = time.perf_counter()
            events = list(client.stream({**SPEC, "seed": seed}))
            wall = time.perf_counter() - t
            kinds = [ev.get("event") for ev in events]
            check("error" not in kinds,
                  f"{label} request: error event {events[-1]}")
            check(kinds[-1:] == ["done"],
                  f"{label} request ended with {kinds[-1:]}, not done")
            res = transport.collect(events)
            check(res.retries == 0,
                  f"{label} request needed {res.retries} retries")
            finite_scores(res, f"{label} request")
            check(len(res.lead_steps) == SPEC["lead_steps"],
                  f"{label} request served {len(res.lead_steps)} leads")
            if label == "warm":
                check(res.timing["compile_s"] == 0.0,
                      f"warm request compiled for "
                      f"{res.timing['compile_s']} s")
            crps = float(res.scores["crps"].mean())
            say(dev, f"{label} request {wall:.2f} s wall, compile_s "
                     f"{res.timing['compile_s']}, mean CRPS {crps:.4f}; "
                     f"peak {peak_gb(jax.devices()[0])}")
        stats = client.stats()
    finally:
        server.shutdown()
        server.server_close()
    engines = [e for e in stats["engines"] if e["config"] == "full"]
    check(len(engines) == 1, f"expected one full engine, got {engines}")
    eng = engines[0]
    check(eng["dispatch"]["jit"] == 0,
          f"engine dispatched {eng['dispatch']['jit']} jit calls")
    check(eng["kernels"] == {"sht": "pallas", "disco": "pallas"},
          f"dispatch report {eng['kernels']}, want compiled pallas")
    say(dev, f"dispatch {eng['kernels']}, jit {eng['dispatch']['jit']}, "
             f"aot {eng['dispatch']['aot']}")
    bundle = service.scheduler.pool.get("full")
    service.close()
    return bundle


def parity_phase(dev: dict, bundle) -> None:
    """One step of the served params and initial state: Pallas dispatch
    (as served) vs the float32 reference dispatch at "highest", compared
    on the decoder output before the water-channel softclamp."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.blocks import softclamp
    from repro.core.fcn3 import FCN3
    from repro.kernels.config import KernelConfig

    model, ds = bundle.model, bundle.ds
    state = ds.state(0, 0)[None]
    cond = jnp.concatenate(
        [jnp.asarray(ds.aux_fields(0.0))[None],
         model.sample_noise(jax.random.PRNGKey(1), (1,))], axis=1)
    t = time.perf_counter()
    got = jax.jit(functools.partial(model.apply, clamp_water=False))(
        bundle.params, bundle.buffers, state, cond)
    got.block_until_ready()
    t_pal = time.perf_counter() - t
    ref_model = FCN3(dataclasses.replace(
        model.cfg, kernels=KernelConfig(sht="reference", disco="reference")))
    ref_buffers = ref_model.make_buffers()
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(ref_model.apply, clamp_water=False))(
            bundle.params, ref_buffers, state, cond)
        want.block_until_ready()
    t_ref = time.perf_counter() - t
    water = model.cfg.water_channel_indices()
    got_c = np.asarray(got.at[:, water].set(softclamp(got[:, water])))
    want_c = np.asarray(want.at[:, water].set(softclamp(want[:, water])))
    got, want = np.asarray(got), np.asarray(want)
    check(bool(np.isfinite(got).all()), "pallas step is not finite")
    check(bool(np.isfinite(want).all()), "reference step is not finite")

    def rel_rms(a, b):
        err = np.sqrt(((a - b) ** 2).mean(axis=(0, 2, 3)))
        scale = np.sqrt((b ** 2).mean(axis=(0, 2, 3)))
        return err, err / np.maximum(scale, 1e-12)

    err, rel = rel_rms(got, want)
    err_c, rel_c = rel_rms(got_c, want_c)
    worst = int(rel.argmax())
    worst_c = int(rel_c.argmax())
    say(dev, f"parity (decoder output): max per-channel relative RMS "
             f"{rel.max():.3e} (channel {worst}), median "
             f"{np.median(rel):.3e}, bound {PARITY_RTOL}; max absolute "
             f"RMS {err.max():.3e}; pallas step {t_pal:.1f} s, reference "
             f"step {t_ref:.1f} s incl. compile; peak "
             f"{peak_gb(jax.devices()[0])}")
    say(dev, f"after the softclamp: max per-channel relative RMS "
             f"{rel_c.max():.3e} (channel {worst_c}: absolute RMS "
             f"{err_c[worst_c]:.3e}, reference zero on "
             f"{(want_c[:, worst_c] == 0).mean():.1%} of points), median "
             f"{np.median(rel_c):.3e}; not bounded (see PARITY_RTOL)")
    check(float(rel.max()) <= PARITY_RTOL,
          f"pallas vs reference step: relative RMS {rel.max():.3e} on "
          f"channel {worst} exceeds {PARITY_RTOL}")


# ---------------------------------------------------------------------------
# Four chips: member sharding
# ---------------------------------------------------------------------------

def sharding_phase(dev: dict, devices, config: str = "small",
                   members: int = 4, steps: int = 4) -> None:
    """One ensemble, members sharded over ``devices`` (a 1-D mesh), vs
    the same rollout on ``devices[0]`` alone.  ``small`` (181x360, 5
    levels) with 4 members is a configuration one chip holds, so both
    runs are the same work."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.inference import EngineConfig, ForecastEngine
    from repro.serving.scheduler import build_bundle

    n = len(devices)
    check(members % n == 0, f"{members} members do not split over {n}")
    bundle = build_bundle(config)
    model, ds = bundle.model, bundle.ds
    state0 = ds.state(0, 0)
    key = jax.random.PRNGKey(7)

    def aux(s):
        return ds.aux_fields(6.0 * (s + 1))

    def truth(s):
        return ds.state(0, s + 1)

    def run(engine):
        t = time.perf_counter()
        res = engine.forecast(bundle.params, bundle.buffers, state0, aux,
                              key, steps=steps, truth=truth)
        jax.block_until_ready(res.final_state)
        return res, time.perf_counter() - t

    with jax.default_matmul_precision("highest"):
        with jax.default_device(devices[0]):
            one, t_one = run(ForecastEngine(
                model, EngineConfig(members=members, lead_chunk=2)))
        mesh = Mesh(np.asarray(devices), ("members",))
        with jax.set_mesh(mesh):
            shard, t_shard = run(ForecastEngine(
                model, EngineConfig(members=members, lead_chunk=2,
                                    member_axes=("members",))))
    held = {d.id for d in shard.final_state.sharding.device_set}
    check(held == {d.id for d in devices},
          f"final state lives on devices {sorted(held)}, want all {n}")
    shapes = {s.data.shape for s in shard.final_state.addressable_shards}
    check(shapes == {(members // n,) + shard.final_state.shape[1:]},
          f"member shards have shapes {shapes}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    check(all(b is not None and b > 0 for b in in_use),
          f"per-device bytes in use {in_use}: not every device holds a "
          f"share")
    diffs = {}
    for name in ("crps", "ens_rmse", "ssr"):
        a = np.asarray(shard.scores[name])
        b = np.asarray(one.scores[name])
        check(bool(np.isfinite(a).all()), f"sharded {name} not finite")
        diffs[name] = float(np.abs(a - b).max()
                            / max(np.abs(b).max(), 1e-12))
    fa = np.asarray(shard.final_state)
    fb = np.asarray(one.final_state)
    diffs["state"] = float(np.abs(fa - fb).max()
                           / max(np.abs(fb).max(), 1e-12))
    worst = max(diffs.values())
    per = ", ".join(f"{k} {v:.3e}" for k, v in diffs.items())
    say(dev, f"member sharding ({config}, {members} members over {n} "
             f"devices, {steps} steps, float32): max relative difference "
             f"vs one device {worst:.3e} ({per}; bound {SHARD_RTOL}); "
             f"shards {shapes}; "
             f"bytes in use per device {in_use}; wall sharded "
             f"{t_shard:.1f} s, one device {t_one:.1f} s (incl. compile)")
    check(worst <= SHARD_RTOL,
          f"sharded vs one-device rollout differ by {worst:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path at full width; 4: only the "
                         "member-sharding phase and its one-chip "
                         "comparison")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: FAIL: no repro package under {src}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, src)
    try:
        dev = find_tpu(args.chips)
        say(dev, f"jax {__import__('jax').__version__}; "
                 f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}")
        if args.chips == 4:
            import jax
            sharding_phase(dev, jax.devices()[:4])
        else:
            bundle = serve_phase(dev)
            parity_phase(dev, bundle)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
