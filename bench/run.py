#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload full_forecast --seed 7 \\
        --seconds 20 --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The configuration names its model
family, whose modules hold everything model-specific
(``bench/family.py``).  The run, all in this one process:

1. the weights, made once per checkout from the configuration by the
   family's reference (``bench/reference/<family>.py``) and kept as a
   checkpoint under ``bench/.cache/`` (the benchmark's data, not
   set-up);
2. set-up (``setup_s``): the forecast service started with the
   launcher's own code (``repro.launch.service.build_service``) on the
   checkpoint, the cell's request shape warmed, and warm requests over
   HTTP.  Requests carry the configuration's ``request`` fields (its
   precision) and the traffic's; the process keeps JAX's default
   matmul precision, as a deployment does;
3. the window: the traffic's generator (``bench/loadgen/<kind>.py``)
   drives ``POST /v1/forecast`` over localhost for ``--seconds``; with
   ``--trace 1`` a ``jax.profiler`` trace covers exactly the window;
4. peak device memory is read, the service is closed and every device
   array it held is freed;
5. the check: a seeded sample of the window's requests is rolled again
   by the family's float32 reference at "highest" and compared
   (``bench/check.py``, limits in ``bench/checks/<workload>.json``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with a trace), and
last ``checks``, each compared number beside its limit; the same numbers
end stderr.  JAX's compilation cache is kept in ``bench/.cache/jax``.
A run that finds no TPU, or fewer chips than the cell asks for, exits 3
and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import random
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE = os.path.join(BENCH, ".cache")

#: a run that has not ended by now has hung: dump stacks and exit
WATCHDOG_S = 1150


class NoChip(RuntimeError):
    """The machine has no TPU, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def find_cell(name: str) -> tuple[dict, dict, dict, dict, dict]:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg = load_json(BENCH, "configs", f"{cell['config']}.json")
    traffic = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    check = load_json(BENCH, "checks", f"{name}.json")
    return bench, cell, cfg, traffic, check


def find_devices(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"first JAX device is {devs[0].platform!r} "
                     f"({devs[0].device_kind}), not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _flat(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(q, "key", getattr(q, "idx", q)))
                     for q in path): leaf for path, leaf in flat}


def weights(cfg: dict) -> str:
    """The configuration's weights as a checkpoint directory the
    launcher's ``--ckpt`` restores, made once per checkout by the
    family's ``make_weights`` and kept under the directory its
    ``weights_key`` names."""
    import numpy as np

    from bench import family
    ref = family.reference(cfg)
    key, made_with = ref.weights_key(cfg)
    path = os.path.join(CACHE, "weights", cfg["name"], key, "ckpt_00000000")
    if os.path.exists(os.path.join(path, "manifest.json")):
        return path
    reference = ref.Reference(ref.Config.from_dict(cfg["model"]))
    params = ref.make_weights(reference, cfg)
    flat = {f"params/{k}": np.asarray(v) for k, v in _flat(params).items()}
    tmp = path + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    np.savez(os.path.join(tmp, "arrays.npz"), **flat)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump({"step": 0, "keys": sorted(flat), "shardings": {},
                   "extra": {"config": cfg["name"], **made_with}}, f)
    shutil.rmtree(path, ignore_errors=True)
    os.replace(tmp, path)
    del reference, params
    free_device()
    return path


def read_params(path: str, reference) -> dict:
    """The checkpoint ``weights`` wrote, as device arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    template = jax.eval_shape(reference.model.init, jax.random.PRNGKey(0))
    data = np.load(os.path.join(path, "arrays.npz"))
    leaves = [jnp.asarray(data[f"params/{k}"]) for k in _flat(template)]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template), leaves)


# ---------------------------------------------------------------------------
# The service and its client
# ---------------------------------------------------------------------------

def check_config(cfg: dict) -> None:
    """The service's named configuration (``repro.configs.<family>``)
    must be the one in the file."""
    from bench import family
    svc = family.service_configs(cfg)[cfg["named_config"]]()
    diff = {k: (v, getattr(svc, k, None)) for k, v in cfg["model"].items()
            if getattr(svc, k, None) != v}
    if diff:
        raise SystemExit(f"service config {cfg['named_config']!r} differs "
                         f"from {cfg['name']}.json: {diff} (file, service)")


def start_service(cfg: dict, traffic: dict, ckpt: str, warm: dict):
    import repro.launch.service as launcher
    argv = (["--config", cfg["named_config"], "--port", "0",
             "--ckpt", ckpt, "--warm", json.dumps(warm),
             "--log-level", "WARNING"]
            + cfg["service_flags"] + traffic["service_flags"])
    args = launcher.build_parser().parse_args(argv)
    service, startup = launcher.build_service(args)
    from repro.serving.scheduler import RequestSpec
    spec = RequestSpec.from_dict(warm)
    for b in range(2, args.max_batch):
        service.scheduler.warmup(spec, batch=b)
    server = service.make_server("127.0.0.1", 0)
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="bench-http").start()
    return service, server, startup, args.max_batch


def make_send(port: int, scheduler):
    """``send(record, stop_at)``: stream one request into its record.

    Every event is kept with the time it reached this client; with
    ``stop_at`` a stream still open then is cancelled through the
    scheduler (the rollout stops at its next chunk) and closed."""
    import jax

    from repro.serving.client import ForecastClient
    client = ForecastClient(port=port, read_timeout=600.0, resume=False)

    def send(rec: dict, stop_at: float | None) -> None:
        rec["sent"] = time.perf_counter()
        gen = client.stream(rec["spec"])
        try:
            while True:
                # what this client waits for, in the trace's host plane
                label = ("bench:await_chunk" if rec["events"]
                         else "bench:await_start")
                with jax.profiler.TraceAnnotation(label):
                    ev = next(gen, None)
                if ev is None:
                    break
                now = time.perf_counter()
                rec["events"].append((now, ev))
                if stop_at is not None and now >= stop_at:
                    rec["closed_early"] = ev.get("event") not in (
                        "done", "error")
                    stream = scheduler.stream_by_id(ev.get("request_id", ""))
                    if rec["closed_early"] and stream is not None:
                        stream.cancel()
                    break
        except Exception as e:  # noqa: BLE001 - a failed request is data
            rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            gen.close()

    return client, send


def warm_up(send, base_spec: dict, max_batch: int) -> float:
    """Warm requests over HTTP, in bursts of 1 .. max_batch at once so
    each coalesced batch size has run (twice when there are bursts, as
    a burst need not coalesce); returns the largest compile_s the last
    round reported (0.0 when every program was warm)."""
    from bench.loadgen.common import new_record
    worst = 0.0
    rounds = 1 if max_batch == 1 else 2
    for rnd in range(rounds):
        for b in range(1, max_batch + 1):
            recs = [new_record(time.perf_counter(),
                               {**base_spec, "sample": 10**7 + i,
                                "seed": 10**7 + i}) for i in range(b)]
            th = [threading.Thread(target=send, args=(r, None))
                  for r in recs]
            for t in th:
                t.start()
            for t in th:
                t.join()
            for r in recs:
                if r["error"]:
                    raise RuntimeError(f"warm request failed: {r['error']}")
                for _t, ev in r["events"]:
                    if ev.get("event") == "start" and rnd == rounds - 1:
                        worst = max(worst, float(ev.get("compile_s", 0.0)))
    return worst


def free_device() -> None:
    """Delete every live device array (the service is closed)."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------------------
# Reading a window
# ---------------------------------------------------------------------------

def summarize(records: list[dict], t0: float, t1: float) -> dict:
    """Member-steps completed in the window and when the last of them
    completed, and which requests failed."""
    steps, last = 0, t0
    failed = 0
    for rec in records:
        members = int(rec["spec"]["members"])
        kinds = [ev.get("event") for _t, ev in rec["events"]]
        bad = bool(rec["error"]) or "error" in kinds or any(
            float(ev.get("compile_s", 0.0)) > 0 for _t, ev in rec["events"]
            if ev.get("event") == "start")
        if not rec["closed_early"] and "done" not in kinds:
            bad = True
        failed += bad
        for t, ev in rec["events"]:
            if ev.get("event") == "chunk" and t0 <= t <= t1:
                steps += members * len(ev["lead_steps"])
                last = max(last, t)
    late = [rec["sent"] - rec["due"] for rec in records if rec["sent"]]
    return {"member_steps": steps, "failed": failed,
            "attempted": len(records), "last_chunk": last,
            "late_max_s": max(late) if late else 0.0}


def reduce_trace(trace_dir: str):
    from bench import tracereduce
    return tracereduce.read(tracereduce.newest_xplane(trace_dir))


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def setup(cfg: dict, traffic: dict) -> dict:
    """Everything before the window: the service started on the
    configuration's weights with the cell's request shape warmed, and
    warm requests.  Making the weights, once per checkout, is the
    benchmark's own data and is not set-up."""
    t_weights = time.perf_counter()
    ckpt = weights(cfg)
    t_setup = time.perf_counter()
    say(f"weights {t_setup - t_weights:.2f} s (made once per checkout)")
    check_config(cfg)
    base_spec = {"config": cfg["named_config"], **cfg.get("request", {}),
                 **traffic["request"]}
    warm = {**base_spec, "lead_steps": min(base_spec["lead_steps"],
                                           base_spec["lead_chunk"])}
    service, server, startup, max_batch = start_service(
        cfg, traffic, ckpt, warm)
    t_service = time.perf_counter() - t_setup
    client, send = make_send(server.server_address[1], service.scheduler)
    warm_compile = warm_up(send, warm, max_batch)
    setup_s = time.perf_counter() - t_setup
    pre = startup["preload"][cfg["named_config"]]
    say(f"set-up {setup_s:.2f} s: service {t_service:.2f} s (host plans "
        f"{pre['plans_s']:.2f} s, weights restore {pre['calibrate_s']:.2f} "
        f"s, warm compile {sum(w['compile_s'] for w in startup['warm']):.2f}"
        f" s), warm requests {setup_s - t_service:.2f} s; their compile_s "
        f"{warm_compile}")
    return {"service": service, "server": server, "client": client,
            "send": send, "base_spec": base_spec, "ckpt": ckpt,
            "setup_s": setup_s}


def drive(st: dict, traffic: dict, seed: int, seconds: float,
          trace_dir: str | None = None) -> tuple[list[dict], float, float]:
    """The window: the traffic's generator for ``seconds`` (traced into
    ``trace_dir`` when given).  Returns the records and the window's
    bounds; requests due in it may end after it."""
    import jax
    gen = importlib.import_module(f"bench.loadgen.{traffic['kind']}")
    rng = random.Random(seed)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    out: dict = {}
    t0 = time.perf_counter() + 0.05
    load = threading.Thread(
        target=lambda: out.update(records=gen.run(
            st["send"], traffic, st["base_spec"], rng, t0, seconds)),
        name="bench-load")
    time.sleep(max(0.0, t0 - time.perf_counter()))
    with jax.profiler.TraceAnnotation("bench:window"):
        load.start()
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    if trace_dir:
        jax.profiler.stop_trace()
    load.join()
    return out["records"], t0, t0 + seconds


def teardown(st: dict, free: bool = True) -> None:
    """Stop the service; with ``free``, delete every device array left
    (the service's caches then hold deleted arrays: start no other)."""
    st["server"].shutdown()
    st["server"].server_close()
    st["service"].close()
    st.clear()
    gc.collect()
    if free:
        free_device()


def reference(cfg: dict, ckpt: str):
    """The family's float32 reference of the configuration and its
    weights."""
    from bench import family
    ref = family.reference(cfg)
    r = ref.Reference(ref.Config.from_dict(cfg["model"]))
    return r, read_params(ckpt, r)


def roll(reference, params, spec: dict, leads: int) -> list[dict]:
    """The products of a request's first ``leads`` leads, rolled by
    ``reference`` (at "highest", or its matmuls take bf16 passes)."""
    import jax
    with jax.default_matmul_precision("highest"):
        return reference.rollout(params, spec["sample"], spec["seed"],
                                 spec["members"], leads,
                                 spec.get("scored", True),
                                 spec.get("spectra", False))


def reference_gaps(cfg: dict, ckpt: str, picked: list,
                   leads: int) -> dict[str, float]:
    """Roll each picked request again with the float32 reference; the
    widest gaps over them."""
    from bench import check as checklib
    r, params = reference(cfg, ckpt)
    numbers: dict[str, float] = {}
    for rec, served in picked:
        for k, v in checklib.gaps(served, roll(r, params, rec["spec"],
                                               leads)).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    del params, r
    free_device()
    return numbers


def run_cell(args, bench: dict, cell: dict, cfg: dict, traffic: dict,
             check: dict, device: dict, peak: dict) -> dict:
    import jax

    from bench import check as checklib, family

    counts = family.counts(cfg)
    st = setup(cfg, traffic)
    setup_s, ckpt = st["setup_s"], st["ckpt"]
    trace_dir = (os.path.join(CACHE, "trace", cell["name"]) if args.trace
                 else None)
    records, t0, t1 = drive(st, traffic, args.seed, args.seconds, trace_dir)
    summary = summarize(records, t0, t1)
    mem_peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    device = {**device, "memory_peak_bytes": mem_peak}
    teardown(st)

    run = {"records": records, "t0": t0, "t1": t1, "window_s": t1 - t0,
           "busy_to_s": summary["last_chunk"] - t0,
           "member_steps": summary["member_steps"], "model": cfg["model"],
           "counts": counts, "value_bytes": counts.VALUE_BYTES[
               cfg.get("request", {}).get("precision", "float32")],
           "peaks": peak, "device": device, "setup_s": setup_s,
           "summary": summary, "trace": None}
    result: dict = {"correct": False, "attempted": summary["attempted"],
                    "failed": summary["failed"]}
    if args.trace:
        from bench import tracereduce
        trace = reduce_trace(trace_dir)
        run["trace"] = trace
        device["busy_s"] = tracereduce.busy_s(trace)
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": tracereduce.top_ops(trace),
                               "idle_gaps": tracereduce.idle_gaps(trace)}
    metrics = {}
    pkg = "bench.metrics" if args.trace else "bench.e2e"
    for m in bench["per_layer"] if args.trace else bench["end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = importlib.import_module(f"{pkg}.{m['name']}").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    say(f"window {args.seconds} s: attempted {summary['attempted']}, "
        f"failed {summary['failed']}, member-steps "
        f"{summary['member_steps']}, generator late by at most "
        f"{summary['late_max_s']:.4f} s; peak {mem_peak} bytes")

    picked = checklib.pick(records, check["requests"], check["leads"],
                           random.Random(f"check-{args.seed}"))
    t_ref = time.perf_counter()
    numbers = (reference_gaps(cfg, ckpt, picked, check["leads"])
               if len(picked) == check["requests"] else {})
    limits = check["limits"]
    result["correct"] = (checklib.judge(numbers, limits)
                         and summary["failed"] == 0
                         and len(picked) == check["requests"])
    say(f"check: {len(picked)} of {check['requests']} requests, "
        f"{check['leads']} leads each, reference "
        f"{time.perf_counter() - t_ref:.1f} s")
    checks = {k: {"value": numbers.get(k), "limit": lim}
              for k, lim in limits.items()}
    checks["failed_requests"] = {"value": summary["failed"], "limit": 0}
    for k, v in checks.items():
        say(f"check {k}: {v['value']} (limit {v['limit']})")
    result["checks"] = checks
    return result


def peaks(device: dict) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if device["kind"] not in table:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} in "
                         f"bench/peaks.json")
    return table[device["kind"]]


def prepare_process(watchdog_s: float = WATCHDOG_S) -> bool:
    """Watchdog, the compile cache inside the checkout, import paths.
    False when the checkout holds no forecast service to measure."""
    import faulthandler
    faulthandler.dump_traceback_later(watchdog_s, exit=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: no forecast service (src/repro) in this checkout",
              file=sys.stderr)
        return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not prepare_process():
        return 2
    bench, cell, cfg, traffic, check = find_cell(args.workload)
    try:
        device = find_devices(int(cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}; this benchmark measures the chip and has no "
              f"CPU fallback", file=sys.stderr)
        return 3
    result = run_cell(args, bench, cell, cfg, traffic, check, device,
                      peaks(device))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
