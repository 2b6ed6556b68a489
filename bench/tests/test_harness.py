"""A whole run of ``full_forecast`` at the service's CPU test size, the
chip look skipped: an intact service passes the check, and each fault a
served forecast can have fails it, as does the control (the reference
with its matmuls in float8, in the service's place).  The limit is the
one ``full_forecast`` holds on the chip."""

import argparse
import gc
import json
import os

import numpy as np
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
    cfg = _load(HERE, "data", "fcn3_smoke.json")
    traffic = _load(BENCH, "traffic", "forecast_cycle.json")
    check = _load(BENCH, "checks", "full_forecast.json")
    bench = _load(os.path.dirname(BENCH), "BENCHMARK.json")
    return cfg, traffic, check, bench


def _run(cell, seed=5):
    cfg, traffic, check, bench = cell
    c = {"name": "full_forecast", "config": "fcn3_smoke",
         "traffic": "forecast_cycle", "chips": 1}
    args = argparse.Namespace(seed=seed, seconds=2.0, trace=0)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    return bench_run.run_cell(args, bench, c, cfg, traffic, check, device,
                              peak)


def test_intact_service_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res["checks"])[-1] == "failed_requests"
    assert {"member_steps_per_s", "setup_s"} <= set(res["metrics"])


def test_an_answer_altered_where_produced_fails(cell, monkeypatch):
    from repro.serving import transport
    real = transport.chunk_event

    def altered(request_id, index, block):
        ev = real(request_id, index, block)
        spectrum = np.asarray(ev["scores"]["spectrum"])
        spectrum[..., 0, :] *= 1.5
        ev["scores"]["spectrum"] = spectrum.tolist()
        return ev

    monkeypatch.setattr(transport, "chunk_event", altered)
    res = _run(cell)
    assert not res["correct"]
    assert res["checks"]["spectrum_gap"]["value"] > 0.4


def test_a_step_that_returns_its_state_fails(cell, monkeypatch):
    from repro.core import fcn3
    monkeypatch.setattr(fcn3.FCN3, "apply",
                        lambda self, params, buffers, state, cond_in,
                        **kw: state)
    res = _run(cell)
    assert not res["correct"]


@pytest.mark.parametrize("mode", ["control_fp8", "fault_member_copy",
                                  "fault_no_noise"])
def test_the_program_passes_and_each_variant_fails(cell, mode):
    """The reference put in the service's place, with its matmuls in
    float8 (the control), half the ensemble left out, or the noise left
    out: each must read above the limit that the service reads under."""
    from bench import control
    cfg, traffic, check, _bench = cell
    limit = check["limits"]["spectrum_gap"]
    rows = control.readings(cfg, traffic, check, 2.0, [11], [11])
    got = {r["mode"]: r for r in rows}
    assert got["program"]["correct"], got["program"]["numbers"]
    assert got["program"]["numbers"]["spectrum_gap"] < limit
    assert not got[mode]["correct"]
    assert got[mode]["numbers"]["spectrum_gap"] > limit
