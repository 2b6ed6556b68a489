"""A whole run of ``sfno_subseasonal`` at the service's CPU test size
(``sfno_smoke``), the chip look skipped: the intact service passes the
check, and it fails under the float8 control (the reference with its
matmuls in float8, in the service's place) and under an SFNO fault (the
spectral filters' imaginary weights dropped).  The limit is the one
``sfno_subseasonal`` holds on the chip."""

import argparse
import contextlib
import gc
import json
import os

import pytest

from bench import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cell(tmp_path, monkeypatch):
    monkeypatch.setattr(bench_run, "CACHE", str(tmp_path))
    # one process starts several services here: keep the arrays their
    # caches share (a run on the chip frees them before its reference)
    monkeypatch.setattr(bench_run, "free_device", gc.collect)
    cfg = _load(HERE, "data", "sfno_smoke.json")
    traffic = _load(BENCH, "traffic", "deterministic_subseasonal.json")
    check = _load(BENCH, "checks", "sfno_subseasonal.json")
    bench = _load(os.path.dirname(BENCH), "BENCHMARK.json")
    return cfg, traffic, check, bench


@contextlib.contextmanager
def fault_mix_real_only(ref, members: int):
    """The reference in the program's place with every block's filter
    mixing the real and imaginary parts by its real weights alone: the
    complex product loses its imaginary weights."""
    step = ref._step

    def faulty(params, buffers, state):
        blocks = [{**b, "filter": {**b["filter"],
                                   "w_im": 0.0 * b["filter"]["w_im"]}}
                  for b in params["blocks"]]
        return step({**params, "blocks": blocks}, buffers, state)

    ref._step = faulty
    try:
        yield
    finally:
        ref._step = step


def test_intact_service_is_correct(cell):
    cfg, traffic, check, bench = cell
    c = {"name": "sfno_subseasonal", "config": "sfno_smoke",
         "traffic": "deterministic_subseasonal", "chips": 1}
    args = argparse.Namespace(seed=2**31 + 17, seconds=2.0, trace=0)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    res = bench_run.run_cell(args, bench, c, cfg, traffic, check, device,
                             peak)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert {"member_steps_per_s", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("mode", ["control_fp8", "fault_mix_real_only"])
def test_the_program_passes_and_each_variant_fails(cell, monkeypatch,
                                                   mode):
    """Each variant must read above the limit that the service reads
    under, the control by at least twice the limit."""
    from bench import control
    monkeypatch.setattr(control, "VARIANTS", {
        "control_fp8": control.control_fp8,
        "fault_mix_real_only": fault_mix_real_only})
    cfg, traffic, check, _bench = cell
    limit = check["limits"]["spectrum_gap"]
    rows = control.readings(cfg, traffic, check, 2.0, [11], [11])
    got = {r["mode"]: r for r in rows}
    assert got["program"]["correct"], got["program"]["numbers"]
    assert got["program"]["numbers"]["spectrum_gap"] < limit
    assert not got[mode]["correct"]
    assert got[mode]["numbers"]["spectrum_gap"] > 2 * limit
