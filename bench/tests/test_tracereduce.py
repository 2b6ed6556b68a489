"""The reduction from a profiler trace to busy time, gaps and kernels."""

import time

import pytest

from bench import tracereduce as tr


def _trace(ops, notes=(), lo=0.0, hi=100.0):
    return tr.Trace(ops={"/device:TPU:0": list(ops)},
                    annotations=list(notes), lo=lo, hi=hi)


def test_union_merges_overlaps_and_clips_to_the_window():
    got = tr.union([("a", -5, 10), ("b", 5, 20), ("c", 30, 40),
                    ("d", 95, 130)], 0, 100)
    assert got == [(0, 20), (30, 40), (95, 100)]


def test_busy_counts_overlapping_ops_once():
    t = _trace([("a", 10, 30), ("b", 20, 40), ("c", 60, 70)])
    assert t.window_s == pytest.approx(100e-9)
    assert tr.busy_s(t) == pytest.approx(40e-9)


def test_busy_is_averaged_over_devices():
    t = tr.Trace(ops={"/device:TPU:0": [("a", 0, 50)],
                      "/device:TPU:1": [("a", 0, 10)]},
                 annotations=[], lo=0, hi=100)
    assert tr.busy_s(t) == pytest.approx(30e-9)


def test_idle_gaps_are_labelled_by_the_innermost_annotation():
    t = _trace([("a", 10, 30), ("b", 60, 75)],
               notes=[("bench:request", 0, 100), ("bench:stage", 35, 55)])
    assert tr.gaps(t) == [(0, 10), (30, 60), (75, 100)]
    gaps = tr.idle_gaps(t)
    assert [g[0] for g in gaps] == ["bench:stage", "bench:request",
                                    "bench:request"]   # middles 45, 87.5, 5
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 25e-9, 10e-9])
    assert tr.label(t, 200) == "bench:unlabelled"


def test_kernel_seconds_and_top_ops():
    """Events carry HLO text; a loop's event spans its body's ops."""
    disco = "%disco_band_contract.{} = f32[1,8]{{1,0}} custom-call(...)"
    t = _trace([("%while.9 = (s32[], f32[2]) while(...), body=%disco",
                 0, 45),
                (disco.format(31), 0, 10), ("%fusion.3 = f32[2] fusion()",
                                            10, 15),
                (disco.format(7), 20, 30),
                ("%vmap_jit_disco_band_contract__.2 = f32[2,8] custom-call()",
                 30, 40),
                ("%legendre_contract = f32[4] custom-call()", 50, 51)])
    secs, n = tr.kernel_seconds(t, "disco_band_contract")
    assert (secs, n) == (pytest.approx(30e-9), 3)
    assert tr.kernel_seconds(t, "legendre_contract")[1] == 1
    assert tr.kernel_seconds(t, "absent") == (0.0, 0)
    # the window's first 25 ns: the second event is cut at 25, the third
    # lies past it
    assert tr.kernel_seconds(t, "disco_band_contract", 25e-9) == (
        pytest.approx(15e-9), 2)
    top = tr.top_ops(t)
    assert [name for name, _ in top] == [
        "disco_band_contract", "vmap_jit_disco_band_contract__", "fusion",
        "legendre_contract"]
    assert top[0][1] == pytest.approx(20e-9)


def test_reads_a_recorded_trace(tmp_path):
    """A small trace recorded here on the CPU: the window annotation is
    found, ops carrying an ``hlo_op`` stat are read as device ops (the
    CPU runs them on host threads), busy time lies inside the window."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:idle"):
                time.sleep(0.005)
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.read(tr.newest_xplane(str(tmp_path)), device_prefix="/host:CPU")
    assert t.window_s > 0.015
    assert t.ops, "no op events with an hlo_op stat"
    assert 0 < tr.busy_s(t) < t.window_s
    assert any(n == "bench:idle" for n, _a, _b in t.annotations)
    assert {g[0] for g in tr.idle_gaps(t)} <= {"bench:idle",
                                                "bench:unlabelled"}
