"""The names the program writes into a ``jax.profiler`` trace.

* Every op of the engine's chunk program carries one of the named scopes
  of ``repro.telemetry.SCOPES`` in its HLO ``op_name`` metadata (the name
  stack a TPU trace reports as each op's ``tf_op``), so device time can
  be put down to an FCN3 operator.  The Pallas calls' scopes are checked
  where they exist, in ``tests/test_tpu_compile.py``.
* A served request writes its host spans into a running trace as
  ``repro:<span>`` annotations, and writes none with tracing disabled.
"""

import glob
import re
import threading

import jax
import pytest

from repro import telemetry
from repro.configs import fcn3 as fcn3cfg
from repro.core.fcn3 import FCN3
from repro.inference import EngineConfig, ForecastEngine
from repro.serving.cache import ExecutableCache
from repro.serving.client import ForecastClient
from repro.serving.observability import ObservabilityConfig
from repro.serving.scheduler import ForecastScheduler, ModelPool, RequestSpec
from repro.serving.service import ForecastService

#: instructions that compute nothing, left out of the count
PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element")
#: most the unscoped instructions may be of the rest: XLA's own layout
#: copies (no metadata), the scan's plumbing, and the concat of aux and
#: noise into the conditioning (6% at the smoke size)
UNSCOPED_SHARE = 0.10
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = .*?(?P<op>[a-z][\w-]*)\(.*$", re.M)
HOST_SPANS = {"stage_h2d", "dispatch", "score_fetch", "encode",
              "stream_write"}


def scope_of(line: str) -> str | None:
    """The innermost named scope in an HLO line's ``op_name``; a scope
    may come wrapped by a transformation (``vmap(fcn3.encoder)``)."""
    m = re.search(r'op_name="([^"]*)"', line)
    for part in reversed(m.group(1).split("/") if m else []):
        part = part.rstrip(")").rsplit("(", 1)[-1]
        if part in telemetry.SCOPES:
            return part
    return None


@pytest.fixture(scope="module")
def smoke():
    model = FCN3(fcn3cfg.fcn3_smoke())
    return model, jax.jit(model.init)(jax.random.PRNGKey(0)), \
        model.make_buffers()


@pytest.mark.parametrize("batch", [None, 2])
def test_chunk_program_ops_carry_scopes(smoke, batch):
    """Serial and coalesced chunk programs, scored with spectra: every
    FCN3 and engine scope is there and no other family's, every dot and
    convolution sits under one, and the unscoped rest stays under
    ``UNSCOPED_SHARE``."""
    model, params, buffers = smoke
    eng = ForecastEngine(model, EngineConfig(members=2, lead_chunk=1,
                                             spectra=True))
    text = eng.lower_chunk(True, 1, params, buffers,
                           batch=batch).compile().as_text()
    found, unscoped, total = set(), [], 0
    for m in INSTRUCTION.finditer(text):
        if m.group("op") in PLUMBING:
            continue
        total += 1
        scope = scope_of(m.group(0))
        if scope is None:
            unscoped.append(m.group("name"))
            assert m.group("op") not in ("dot", "convolution"), m.group(0)
        found.add(scope)
    assert set(telemetry.FCN3_SCOPES + telemetry.ENGINE_SCOPES) <= found
    assert not found & set(telemetry.SFNO_SCOPES)
    assert len(unscoped) < UNSCOPED_SHARE * total, (len(unscoped), total)


@pytest.fixture(scope="module")
def pool():
    return ModelPool()


@pytest.mark.parametrize("enabled", [True, False])
def test_served_request_writes_host_spans(pool, tmp_path, enabled):
    """One request over HTTP inside a ``jax.profiler`` trace: its host
    spans are in the trace's host plane as ``repro:`` annotations, and
    none is with tracing off (``--no-tracing``)."""
    from jax.profiler import ProfileData
    sched = ForecastScheduler(
        pool=pool, cache=ExecutableCache(), max_concurrency=1,
        observability=ObservabilityConfig(enabled=enabled))
    server = ForecastService(scheduler=sched).make_server(port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    spec = RequestSpec(config="smoke", members=2, lead_steps=2,
                       lead_chunk=1, scored=True)
    try:
        sched.warmup(spec)
        jax.profiler.start_trace(str(tmp_path))
        try:
            events = list(ForecastClient(
                port=server.server_address[1]).stream(spec))
        finally:
            jax.profiler.stop_trace()
    finally:
        server.shutdown()
        server.server_close()
        sched.close()
    assert events[-1]["event"] == "done"
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host") for ln in plane.lines
             for ev in ln.events
             if ev.name.startswith(telemetry.ANNOTATION_PREFIX)}
    spans = {n[len(telemetry.ANNOTATION_PREFIX):] for n in names}
    assert spans >= HOST_SPANS if enabled else spans == set(), spans
