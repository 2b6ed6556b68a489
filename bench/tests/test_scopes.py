"""Device time per named scope, the chunk program's executions and the
program's host spans (``bench.scopes``), and the readers that were there
before them, which must read the same numbers."""

import importlib
import json
import os
import re
import time

import pytest

from bench import family, scopes
from bench import tracereduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# A trace recorded here on the CPU
# ---------------------------------------------------------------------------

def _stacks_from_hlo(text: str) -> dict[str, set[str]]:
    """Per HLO instruction name, its ``op_name``: what a TPU trace gives
    as each op's ``tf_op`` stat, which a CPU trace does not carry."""
    out = {}
    for m in re.finditer(r'^\s*(?:ROOT )?%(\S+) = .*op_name="([^"]*)"',
                         text, re.M):
        out.setdefault(m.group(1), set()).add(m.group(2))
    return out


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Three calls of a jitted function with two scopes, inside the
    window; the host waits (``bench:await_chunk``) and stages
    (``repro:stage_h2d``, open inside the wait) between calls."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("fcn3.encoder"):
            y = jnp.sin(x) @ x
        with jax.named_scope("fcn3.decoder"):
            return jnp.tanh(y @ y) + 1.0

    jf = jax.jit(f)
    x = jnp.ones((256, 256))
    jf(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:await_chunk"):
                with jax.profiler.TraceAnnotation("repro:stage_h2d"):
                    time.sleep(0.005)
            jf(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.newest_xplane(d)
    return (d, path, tr.read(path, device_prefix="/host:CPU"),
            _stacks_from_hlo(jf.lower(x).compile().as_text()))


def test_ops_map_to_their_scopes(recorded):
    _d, _path, trace, stacks = recorded
    got = {}
    for name, _a, _b in trace.ops["/host:CPU"]:
        if tr.op_family(name) not in tr.CONTAINERS:
            got[name] = scopes._scope(stacks.get(name))
    dots = {n: s for n, s in got.items() if n.startswith("dot_general")}
    assert sorted(dots.values()) == ["fcn3.decoder", "fcn3.encoder"]
    assert "fcn3.encoder" in {s for n, s in got.items() if "sine" in n}
    secs, counts = scopes.scope_seconds(trace, stacks)
    assert {"fcn3.encoder", "fcn3.decoder"} <= set(secs)
    assert counts["fcn3.encoder"] >= 6      # a dot and a sine per call


def test_scopes_partition_op_time(recorded):
    _d, _path, trace, stacks = recorded
    secs, _ = scopes.scope_seconds(trace, stacks)
    assert sum(secs.values()) == pytest.approx(
        sum(tr.op_seconds(trace).values()), rel=1e-12)
    # up to a cut the scopes hold no more than the whole window does
    cut, _ = scopes.scope_seconds(trace, stacks, trace.window_s / 2)
    assert 0 < sum(cut.values()) < sum(secs.values())


def test_program_spans_label_the_gaps(recorded):
    _d, path, trace, _stacks = recorded
    profile = scopes.load(path, device_prefix="/host:CPU")
    assert (profile.lo, profile.hi) == (trace.lo, trace.hi)
    names = {n for n, _a, _b in trace.annotations}
    assert names == {"bench:await_chunk", "repro:stage_h2d"}
    labels = {g[0] for g in tr.idle_gaps(trace)}
    assert "repro:stage_h2d" in labels
    assert "bench:await_chunk" not in labels   # the program span wins
    assert profile.stacks == {}                # no tf_op stat on the CPU


def test_program_spans_change_the_labels_alone(recorded):
    """The trace read with the program's spans and as it was read
    without them: busy time, idle share and the gaps are the same to
    the bit, and only the gaps' labels differ."""
    from bench.metrics import device_idle_share
    _d, _path, trace, _stacks = recorded
    bench_only = tr.Trace(ops=trace.ops, lo=trace.lo, hi=trace.hi,
                          annotations=[n for n in trace.annotations
                                       if n[0].startswith(tr.PREFIX)])
    assert len(bench_only.annotations) < len(trace.annotations)
    assert tr.busy_s(trace) == tr.busy_s(bench_only)
    assert device_idle_share.read({"trace": trace}) == \
        device_idle_share.read({"trace": bench_only})
    gaps, before = tr.idle_gaps(trace), tr.idle_gaps(bench_only)
    assert [g[1] for g in gaps] == [g[1] for g in before]
    assert [g[0] for g in gaps] != [g[0] for g in before]


def test_label_prefers_an_open_program_span():
    trace = tr.Trace(ops={}, lo=0, hi=300, annotations=[
        ("bench:await_chunk", 0, 100), ("repro:dispatch", 10, 60),
        ("repro:stage_h2d", 20, 30), ("bench:inner", 70, 80)])
    assert tr.label(trace, 25) == "repro:stage_h2d"
    assert tr.label(trace, 40) == "repro:dispatch"
    assert tr.label(trace, 75) == "bench:inner"
    assert tr.label(trace, 90) == "bench:await_chunk"
    assert tr.label(trace, 200) == "bench:unlabelled"


def test_for_run_finds_the_runs_trace(recorded, monkeypatch):
    d, _path, trace, _stacks = recorded
    monkeypatch.setattr(scopes, "TRACES", d)
    assert scopes.for_run({"trace": trace}).lo == trace.lo
    other = tr.Trace(ops=trace.ops, annotations=[], lo=trace.lo + 1,
                     hi=trace.hi)
    assert scopes.for_run({"trace": other}) is None
    assert scopes.for_run({"trace": None}) is None


# ---------------------------------------------------------------------------
# Event metadata as a TPU trace writes it
# ---------------------------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message from (field number, int | str | bytes)."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name: str, events: list, stat_names: dict) -> bytes:
    """An XPlane: event metadata (id, name, [(stat id, value)]) and stat
    metadata; a str value is a str_value, an int a ref_value."""
    fields = [(2, name)]
    for sid, sname in stat_names.items():
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    for eid, ename, stats in events:
        st = [(5, _msg((1, sid), (5 if isinstance(v, str) else 7, v)))
              for sid, v in stats]
        fields.append((4, _msg((1, eid), (2, _msg((1, eid), (2, ename),
                                                   *st)))))
    return _msg(*fields)


def test_name_stacks_read_tf_op(tmp_path):
    stat_names = {1: "tf_op", 2: "program_id",
                  9: "jit(chunk)/fcn3.mlp/dot_general"}
    dev = _plane("/device:TPU:0", [
        (1, "%disco.1 = custom-call()",
         [(2, 7), (1, "jit(chunk)/while/body/fcn3.decoder/pallas_call")]),
        (2, "%fusion.2 = fusion()", [(1, 9)]),                # by ref
        (3, "%copy.3 = copy()", [(2, 7)]),                    # no tf_op
        (4, "%fusion.9 = fusion()", [(1, "jit(chunk)/fcn3.mlp/add")]),
        (5, "%fusion.9 = fusion()", [(1, "jit(other)/fcn3.encoder/add")]),
    ], stat_names)
    host = _plane("/host:CPU", [(1, "%x = y()", [(1, "jit(f)/fcn3.mlp")])],
                  stat_names)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, dev)))
    stacks = scopes.name_stacks(str(path))
    assert stacks == {
        "%disco.1 = custom-call()": {
            "jit(chunk)/while/body/fcn3.decoder/pallas_call"},
        "%fusion.2 = fusion()": {"jit(chunk)/fcn3.mlp/dot_general"},
        "%fusion.9 = fusion()": {"jit(chunk)/fcn3.mlp/add",
                                 "jit(other)/fcn3.encoder/add"}}
    # two programs name one op differently: it goes to neither
    assert scopes.conflicts(stacks) == ["%fusion.9 = fusion()"]
    assert scopes._scope(stacks["%fusion.9 = fusion()"]) == scopes.UNSCOPED
    assert scopes._scope(stacks["%disco.1 = custom-call()"]) == \
        "fcn3.decoder"


def test_scope_of_takes_the_innermost_vocabulary_name():
    assert scopes.scope_of("jit(chunk)/while/body/engine.products/"
                           "jit(fcn3.mlp)/x") == "fcn3.mlp"
    assert scopes.scope_of("jit(apply)/vmap(fcn3.encoder)/jit(disco)/"
                           "pallas_call") == "fcn3.encoder"
    assert scopes.scope_of("jit(chunk)/while/body/concatenate") is None


def test_the_vocabulary_is_the_programs():
    from repro import telemetry
    assert scopes.SCOPES == telemetry.SCOPES
    assert tr.PROGRAM == telemetry.ANNOTATION_PREFIX


# ---------------------------------------------------------------------------
# The readers on a synthetic trace
# ---------------------------------------------------------------------------

DISCO = "%disco_band_contract.{} = f32[1,8]{{1,0}} custom-call(...)"


def _run(profile=None) -> dict:
    """A 100 s window: a loop (0-45 s) over a DISCO call, a fusion and a
    Legendre call; a gap; a DISCO call and a copy; a gap to the end; 6
    member-steps, the last completion at 90 s."""
    with open(os.path.join(HERE, "data", "fcn3_smoke.json")) as f:
        cfg = json.load(f)
    trace = tr.Trace(ops={"/device:TPU:0": [
        ("%while.9 = (s32[]) while(...)", 0, 45e9),
        (DISCO.format(1), 1e9, 31e9),
        ("%fusion.3 = f32[2] fusion()", 31e9, 40e9),
        ("%legendre_contract.2 = f32[4] custom-call()", 40e9, 44e9),
        (DISCO.format(2), 50e9, 80e9), ("%copy.1 = f32[2] copy()", 80e9,
                                        85e9)]},
        annotations=[("bench:await_chunk", 40e9, 52e9)], lo=0.0, hi=100e9)
    t0 = 1000.0
    events = [(t0 + t, {"event": "chunk", "chunk_s": c, "lead_steps": [i]})
              for i, (t, c) in enumerate([(30, 30.0), (60, 29.0),
                                          (90, 31.5)])]
    return {"records": [{"events": events}], "t0": t0, "t1": t0 + 100,
            "window_s": 100.0, "busy_to_s": 90.0, "member_steps": 6,
            "model": cfg["model"], "counts": family.counts(cfg),
            "value_bytes": 2,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "device": {"memory_peak_bytes": 12_506_000_000},
            "trace": trace}


#: what the readers that were there before this module read on ``_run``,
#: as they read it before the program had named scopes
BEFORE = {"device_idle_share": 19.999999999999996, "peak_hbm_gb": 12.506,
          "step_mfu": 5.240657868020305e-06,
          "disco_roofline": 5.708595848595848e-06,
          "legendre_roofline": 2.4568498168498165e-05,
          "chunk_interval_p50_s": 30.0}


def test_the_earlier_readers_read_the_same():
    run = _run()
    trace = run["trace"]
    assert tr.busy_s(trace) == pytest.approx(80.0)
    assert tr.gaps(trace) == [(45e9, 50e9), (85e9, 100e9)]
    assert tr.op_seconds(trace) == pytest.approx(
        {"disco_band_contract": 60.0, "fusion": 9.0,
         "legendre_contract": 4.0, "copy": 5.0})
    assert tr.kernel_seconds(trace, "disco_band_contract", 60.0) == (
        pytest.approx(40.0), 2)
    for name, value in BEFORE.items():
        got = importlib.import_module(f"bench.metrics.{name}").read(run)
        assert got == pytest.approx(value, rel=1e-12), name


def test_scope_readers(monkeypatch):
    run = _run()
    names = [n for n, _a, _b in run["trace"].ops["/device:TPU:0"]]
    profile = scopes.Profile(
        stacks={names[1]: {"jit(chunk)/fcn3.local_conv/pallas_call"},
                names[2]: {"jit(chunk)/fcn3.mlp/add"},
                names[4]: {"jit(chunk)/fcn3.decoder/pallas_call"},
                names[5]: {"jit(chunk)/while/body/copy"}},
        modules=[("jit_chunk(7)", -5e9, 44e9), ("jit_chunk(7)", 46e9, 86e9),
                 ("jit_other(8)", 46e9, 47e9),
                 ("jit_chunk(7)", 87e9, 130e9),
                 ("jit_chunk(7)", 91e9, 99e9)],
        lo=0.0, hi=100e9)
    monkeypatch.setattr(scopes, "for_run", lambda r: profile)

    def read(name):
        return importlib.import_module(f"bench.metrics.{name}").read(run)

    # up to the last completion (90 s), per member-step, in ms
    assert read("local_conv_ms") == pytest.approx(30e3 / 6)
    assert read("mlp_ms") == pytest.approx(9e3 / 6)
    assert read("decoder_ms") == pytest.approx(30e3 / 6)
    for absent in ("encoder_ms", "spectral_conv_ms", "noise_ms",
                   "products_ms"):
        assert read(absent) is None, absent
    # unscoped: the Legendre call (4 s) and the copy (5 s) of 78 s
    assert read("unscoped_share") == pytest.approx(100 * 9 / 78)
    # only the one chunk execution inside the window and ended by the
    # last completion
    assert read("chunk_device_s") == pytest.approx(40.0)
    assert scopes.module_runs(profile) == pytest.approx([40.0, 8.0])
    assert scopes.module_runs(profile, span_s=80.0) == []
    # a program without named scopes: the scope readers read nothing
    profile.stacks = {}
    assert read("decoder_ms") is None and read("unscoped_share") is None
