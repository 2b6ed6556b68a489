#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, its control and its
faults against the float32 reference, over many seeds, in one process.

    python3 bench/control.py --workload full_forecast --seconds 8 \\
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --variant-seeds 1 2 3

For each seed the cell's traffic runs a short window at the cell's own
load through the service, run as the configuration states it, and the
requests a run with that seed would check are compared with the
reference (``bench/check.py``).  On the variant seeds the same requests
are also rolled by the reference put in the program's place, changed:

* ``control_fp8``: every matmul's operands rounded to float8
  (``bench/fp8.py``), the precision below the served bfloat16;
* ``fault_member_copy``: each lead's member 0 served as every member
  (half the ensemble left out, the mean taken over the rest);
* ``fault_no_noise``: the stochastic forcing left out (zero noise).

One JSON line per (mode, seed) goes to stdout, with the widest gaps,
the verdict of the cell's limits on them and where each gap lies; the
benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as bench_run  # noqa: E402


@contextlib.contextmanager
def control_fp8(ref, members: int):
    from bench import fp8
    step = ref._step
    ref._step = fp8.quantized(ref.model.apply)
    try:
        yield
    finally:
        ref._step = step


@contextlib.contextmanager
def fault_member_copy(ref, members: int):
    step, calls, first = ref._step, [0], [None]

    def copy(*args):
        i = calls[0] % members
        calls[0] += 1
        if i == 0:
            first[0] = step(*args)
        return first[0]

    ref._step = copy
    try:
        yield
    finally:
        ref._step = step


@contextlib.contextmanager
def fault_no_noise(ref, members: int):
    import jax.numpy as jnp
    noise = ref._noise
    ref._noise = lambda z_hat, pct: jnp.zeros_like(noise(z_hat, pct))
    try:
        yield
    finally:
        ref._noise = noise


VARIANTS = {"control_fp8": control_fp8,
            "fault_member_copy": fault_member_copy,
            "fault_no_noise": fault_no_noise}


def readings(cfg: dict, traffic: dict, check: dict, seconds: float,
             seeds: list[int], variant_seeds: list[int]) -> list[dict]:
    """[{mode, seed, numbers, correct, ...}]: "program" for every seed,
    and each of ``VARIANTS`` for the variant seeds."""
    from bench import check as checklib
    st = bench_run.setup(cfg, traffic)
    groups = []
    for seed in seeds:
        records, _t0, _t1 = bench_run.drive(st, traffic, seed, seconds)
        groups.append(checklib.pick(records, check["requests"],
                                    check["leads"],
                                    random.Random(f"check-{seed}")))
    ckpt = st["ckpt"]
    bench_run.teardown(st)
    ref, params = bench_run.reference(cfg, ckpt)
    leads = check["leads"]
    rows = []

    def row(mode, seed, pairs, t):
        numbers: dict[str, float] = {}
        detail = []
        for got, want in pairs:
            for k, v in checklib.gaps(got, want).items():
                numbers[k] = max(numbers.get(k, 0.0), v)
            detail.append(_summary(checklib.gap_table(got, want)))
        ok = (len(pairs) == check["requests"]
              and checklib.judge(numbers, check["limits"]))
        rows.append({"mode": mode, "seed": seed, "numbers": numbers,
                     "correct": ok, "picked": len(pairs),
                     "seconds": time.perf_counter() - t, "detail": detail})

    for seed, picked in zip(seeds, groups):
        t = time.perf_counter()
        wants = [bench_run.roll(ref, params, rec["spec"], leads)
                 for rec, _ in picked]
        row("program", seed, [(s, w) for (_, s), w in zip(picked, wants)],
            t)
        if seed not in variant_seeds:
            continue
        for mode, variant in VARIANTS.items():
            t = time.perf_counter()
            got = []
            try:
                for rec, _ in picked:
                    with variant(ref, int(rec["spec"]["members"])):
                        got.append(bench_run.roll(ref, params, rec["spec"],
                                                  leads))
            except Exception as e:  # noqa: BLE001 - a crash is a reading
                rows.append({"mode": mode, "seed": seed, "numbers": {},
                             "correct": False,
                             "error": f"{type(e).__name__}: {e}"[:2000]})
                continue
            row(mode, seed, list(zip(got, wants)), t)
    return rows


def _summary(table: dict) -> dict:
    """Where a request's gaps lie: per product and lead, the median over
    channels and the five widest channels with their gaps."""
    import numpy as np
    out = {}
    for name, t in table.items():
        out[name] = []
        for lead in t:
            top = np.argsort(lead)[::-1][:5]
            out[name].append({"median": float(np.median(lead)),
                              "top": [[int(c), float(lead[c])]
                                      for c in top]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variant-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    bench_run.prepare_process(watchdog_s=3500)
    _bench, cell, cfg, traffic, check = bench_run.find_cell(args.workload)
    try:
        bench_run.find_devices(int(cell["chips"]))
    except bench_run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    for row in readings(cfg, traffic, check, args.seconds, args.seeds,
                        args.variant_seeds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
