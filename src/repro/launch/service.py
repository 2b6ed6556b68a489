"""Launch the forecast service (paper Section 5, served).

Starts the HTTP front end over the async scheduler: requests queue,
engines stay warm per shape key (LRU-evicted under
``--engine-budget-mb``), executables are cached (optionally persisted),
same-shape requests coalesce into one batched rollout
(``--max-batch``/``--batch-window-ms``), pickup is QoS-aware
(request ``priority``/``deadline_ms``/``degrade`` fields;
``--aging-ms``/``--degrade-margin-ms`` tune the policy -- see
docs/serving.md#qos), and every response streams scores
chunk-by-chunk as NDJSON.

  PYTHONPATH=src python -m repro.launch.service --config smoke --port 8771

then, from anywhere::

  python -m repro.serving.client --port 8771 --members 2 --lead-steps 4

``--persist-dir D`` persists compiled chunk programs across processes
as ``jax.export`` blobs of the lowered StableHLO (skips Python
tracing); the backend compile is served by the process's one XLA
compilation cache (``repro.compile_cache``: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache/`` in the checkout), so a restarted service
warm-starts from disk.  ``--warm SPEC_JSON`` compiles executables for a
request shape before the server accepts traffic.

``--bundle PATH`` boots a zero-cold-start replica from a warm-start
bundle built by ``python -m repro.launch.bundle build``: the manifest
is verified against this process (jax version, backend, source
fingerprint, file hashes -- any mismatch refuses with a diagnostic
instead of silently recompiling), the packed geometry plans are
installed, and every bundled engine is pre-warmed from the StableHLO
blobs over a *readonly* executable cache before the server accepts
traffic.  See docs/deployment.md for the bundle lifecycle.

See docs/serving.md for the API and the NDJSON event grammar.
"""

from __future__ import annotations

import argparse
import json
import logging

from repro.configs import registry

_log = logging.getLogger("repro.launch.service")


def build_parser() -> argparse.ArgumentParser:
    """The launcher's command line (shared with ``chip_smoke.py``)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8771,
                    help="0 picks an ephemeral port (printed at startup)")
    ap.add_argument("--config", nargs="+", default=["smoke"],
                    choices=sorted(registry.named_configs()),
                    help="configs to preload (model + params built at "
                         "startup, not on first request)")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint for the first --config entry")
    ap.add_argument("--max-concurrency", type=int, default=1,
                    help="worker threads running device work")
    ap.add_argument("--queue-size", type=int, default=64,
                    help="pending requests before 503")
    ap.add_argument("--max-batch", type=int, default=1,
                    help="coalesce up to this many queued same-shape "
                         "requests into one batched rollout dispatch "
                         "(1 disables coalescing)")
    ap.add_argument("--batch-window-ms", type=float, default=0.0,
                    help="how long a picked request waits for same-shape "
                         "companions before rolling (latency spent to "
                         "fill batches; 0 coalesces only what is "
                         "already queued)")
    ap.add_argument("--engine-budget-mb", type=float, default=None,
                    help="LRU-evict cold engines when the pool's "
                         "estimated bytes exceed this budget "
                         "(default: unbounded)")
    ap.add_argument("--aging-ms", type=float, default=2000.0,
                    help="a batch-priority request waiting this long is "
                         "promoted to interactive at pickup "
                         "(anti-starvation; 0 restores pure FIFO)")
    ap.add_argument("--degrade-margin-ms", type=float, default=None,
                    help="opted-in requests within this margin of their "
                         "deadline serve the validated member-count "
                         "floor instead of missing (default: within "
                         "25%% of the total deadline budget)")
    ap.add_argument("--persist-dir", default=None,
                    help="persist compiled chunk programs here as "
                         "jax.export blobs (the XLA compilation cache is "
                         "repro.compile_cache's: JAX_COMPILATION_CACHE_DIR "
                         "or .jax_cache/)")
    ap.add_argument("--tuning-dir", default=None, metavar="DIR",
                    help="install this kernel TuningCache (built by "
                         "repro.launch.tune): every engine resolves the "
                         "tuned Pallas tile shapes, which ride the "
                         "engine/executable keys (docs/kernels.md"
                         "#autotuning)")
    ap.add_argument("--tune", action="store_true",
                    help="sweep the preloaded config's hot-op tile "
                         "shapes into --tuning-dir before warmup "
                         "(cache hits skip the sweep; implies "
                         "--tuning-dir .tuning when unset)")
    ap.add_argument("--bundle", default=None, metavar="PATH",
                    help="boot from a warm-start bundle (dir or .tar "
                         "built by repro.launch.bundle): verify, "
                         "install plans, pre-warm every bundled engine "
                         "from its StableHLO blobs; refuses on any "
                         "mismatch instead of recompiling")
    ap.add_argument("--warm", action="append", default=[],
                    metavar="SPEC_JSON",
                    help="RequestSpec JSON to precompile before serving "
                         "(repeatable), e.g. "
                         "'{\"members\": 4, \"lead_steps\": 8}'")
    ap.add_argument("--trace-dir", default=None,
                    help="dump every served request's span tree as "
                         "Chrome/Perfetto trace JSON into this directory "
                         "(traces are also served from memory at "
                         "GET /v1/trace/<request_id>)")
    ap.add_argument("--profile-dir", default=None,
                    help="enable the opt-in per-request jax.profiler "
                         "hook: requests sending 'profile': true get "
                         "their rollout captured as an XLA trace under "
                         "this directory (inert when unset)")
    ap.add_argument("--fault", action="append", default=[],
                    metavar="POINT:SPEC",
                    help="arm a deterministic fault (repeatable), e.g. "
                         "'rollout_chunk:n=2' (fail exactly the 2nd "
                         "chunk), 'import_chunk:first=3,kind=permanent' "
                         "or 'stream_write:p=0.1,seed=7'; see "
                         "repro.serving.faults.FaultSpec.  Unarmed "
                         "points cost nothing")
    ap.add_argument("--retry-backoff-ms", type=float, default=50.0,
                    help="base delay for per-request transient retries "
                         "(exponential: base * 2^(attempt-1), capped)")
    ap.add_argument("--breaker-threshold", type=int, default=3,
                    help="consecutive build/compile failures on one "
                         "engine key before its circuit opens (requests "
                         "shed with reason=circuit_open, no compile)")
    ap.add_argument("--breaker-cooldown-s", type=float, default=30.0,
                    help="seconds an open circuit waits before letting "
                         "one half-open probe through")
    ap.add_argument("--resume-grace-s", type=float, default=15.0,
                    help="seconds a disconnected client may reclaim its "
                         "stream via GET /v1/stream/<id>?from=<seq> "
                         "before the request is cancelled")
    ap.add_argument("--no-tracing", action="store_true",
                    help="disable request tracing and the flight "
                         "recorder (metrics stay on -- they back "
                         "/v1/stats); the instrumented path is free "
                         "when disabled, so this mainly declutters")
    ap.add_argument("--log-level", default="INFO",
                    help="level for the repro.* loggers on stderr")
    return ap


def build_service(args: argparse.Namespace):
    """Start-up of a replica from parsed launcher arguments: compile
    cache, model preload, optional tuning and bundle boot, warmup.
    Returns the ready ``ForecastService`` (not yet listening) and a
    ``startup`` dict of wall times (``preload`` per config with its
    ``plans_s``/``calibrate_s`` split, ``warm`` per warmed spec).
    Raises ``ValueError`` on inconsistent arguments.
    """
    if args.bundle and args.persist_dir:
        raise ValueError("--bundle and --persist-dir are mutually "
                         "exclusive: a bundle replica serves a readonly "
                         "executable set")
    if args.bundle and (args.tune or args.tuning_dir):
        raise ValueError("--bundle and --tune/--tuning-dir are mutually "
                         "exclusive: a bundle replica resolves the "
                         "tunings packed in the bundle")
    if args.tune and not args.tuning_dir:
        args.tuning_dir = ".tuning"

    from repro import compile_cache
    compile_cache.configure()
    from repro.serving.cache import ExecutableCache
    from repro.serving.observability import (ObservabilityConfig,
                                             setup_logging)
    from repro.serving.scheduler import (ForecastScheduler, ModelPool,
                                         RequestSpec)
    from repro.serving.service import ForecastService

    # Logs go to stderr: stdout stays clean for scripted capture.
    setup_logging(args.log_level)
    obs_config = ObservabilityConfig(
        enabled=not args.no_tracing,
        trace_dir=args.trace_dir, profile_dir=args.profile_dir)

    warm_specs = []
    for raw in args.warm:
        try:
            spec = RequestSpec.from_dict(json.loads(raw))
            spec.validate()
        except (ValueError, TypeError, json.JSONDecodeError) as e:
            raise ValueError(f"--warm {raw!r}: {e}") from e
        warm_specs.append(spec)

    faults = None
    if args.fault:
        from repro.serving.faults import FaultInjector
        try:
            faults = FaultInjector.from_args(args.fault)
        except ValueError as e:
            raise ValueError(f"--fault: {e}") from e
        _log.warning("fault injection ARMED: %s (do not deploy this "
                     "replica to production)", args.fault)

    pool = ModelPool({args.config[0]: args.ckpt} if args.ckpt else None)

    if args.tuning_dir:
        # Install before any engine exists: RequestSpec.engine_config()
        # resolves the active cache, so warmup below already compiles
        # the tuned tile shapes (and the tuned engine/executable keys).
        from repro.kernels import autotune
        cache = autotune.TuningCache(args.tuning_dir)
        autotune.install_tuning_cache(cache)
        if args.tune:
            model = pool.get(args.config[0]).model
            sweeps = 0
            for op, all_shapes in autotune.model_op_shapes(model).items():
                for shapes in all_shapes:
                    entry = autotune.sweep_op(op, shapes, cache=cache)
                    sweeps += entry["swept"]
                    _log.info(
                        "tune %s %s: %s (default_us=%.1f best_us=%.1f)",
                        op, "x".join(str(v) for v in shapes),
                        autotune.format_blocks(op, entry["dims"]),
                        entry["default_us"], entry["best_us"])
            _log.info("tuning ready: sweeps=%d %s", sweeps, cache.stats())
        else:
            _log.info("tuning cache installed: %s", cache.stats())

    sched_kwargs = dict(
        max_concurrency=args.max_concurrency, queue_size=args.queue_size,
        max_batch=args.max_batch, batch_window_ms=args.batch_window_ms,
        engine_budget_bytes=(int(args.engine_budget_mb * 2**20)
                             if args.engine_budget_mb is not None
                             else None),
        aging_ms=args.aging_ms,
        degrade_margin_ms=args.degrade_margin_ms,
        observability=obs_config,
        faults=faults,
        retry_backoff_ms=args.retry_backoff_ms,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_s=args.breaker_cooldown_s,
        resume_grace_s=args.resume_grace_s,
        # readiness gate: /readyz stays 503 ("starting") until preload
        # + warmup below finish, so LB traffic probes never route to a
        # replica that would eat a cold compile
        ready=False)
    if args.bundle:
        # Zero-cold-start boot: verify + install plans + pre-warm every
        # bundled engine from StableHLO blobs (readonly cache -- any
        # shape the bundle lacks refuses instead of compiling).
        from repro.serving.bundle import WarmStartBundle, boot_scheduler
        b = WarmStartBundle.load(args.bundle)
        _log.info("booting from bundle %s (%s) ...",
                  b.bundle_id[:12], args.bundle)
        scheduler = boot_scheduler(b, pool=pool, **sched_kwargs)
        info = scheduler.bundle_info
        _log.info("bundle boot OK: %s engine(s), %s program(s), "
                  "%s from blobs, boot_s=%s", info["engines"],
                  info["programs"], info["disk_hits"], info["boot_s"])
    else:
        scheduler = ForecastScheduler(
            pool=pool, cache=ExecutableCache(args.persist_dir),
            **sched_kwargs)
    startup = {"preload": {}, "warm": []}
    for name in args.config:
        _log.info("preloading config %r ...", name)
        built = pool.get(name)
        startup["preload"][name] = built.build_s
        kernels = built.model.cfg.kernels.effective()
        band = (built.model.disco_band_report()
                if kernels["disco"].startswith("pallas")
                and built.model.disco_plans else None)
        _log.info("config %r: kernels %s, disco band runs %s", name,
                  kernels, band)
    for spec in warm_specs:
        out = scheduler.warmup(spec)
        startup["warm"].append({"spec": spec.to_dict(),
                                "compile_s": out["compile_s"]})
        _log.info("warmed %s: compile_s=%.2f (%s)", spec.to_dict(),
                  out["compile_s"],
                  [o["source"] for o in out["outcomes"]])
        if args.max_batch > 1:
            # also warm the full-batch coalesced program, so the first
            # burst of same-shape traffic pays zero compile
            outb = scheduler.warmup(spec, batch=args.max_batch)
            _log.info("warmed batch=%d: compile_s=%.2f (%s)",
                      args.max_batch, outb["compile_s"],
                      [o["source"] for o in outb["outcomes"]])

    # Preload + warmup done: flip /readyz from "starting" to "ready".
    scheduler.mark_ready()
    return ForecastService(scheduler=scheduler), startup


def main(argv=None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)
    try:
        service, _ = build_service(args)
    except ValueError as e:
        ap.error(str(e))
    server = service.make_server(args.host, args.port)
    host, port = server.server_address[:2]
    _log.info("listening on http://%s:%s (POST /v1/forecast, "
              "GET /v1/stats, GET /metrics, GET /healthz, GET /readyz)",
              host, port)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        _log.info("shutting down")
    finally:
        server.server_close()
        service.close()


if __name__ == "__main__":
    main()
