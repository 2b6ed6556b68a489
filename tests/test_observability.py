"""Tests for the observability layer (ISSUE 8).

The load-bearing guarantees:

* the metrics registry renders valid Prometheus text whose values agree
  **exactly** with ``/v1/stats`` -- they are two views of one store;
* a served request's span tree covers its lifetime with no gaps
  (merged child intervals >= 95% of the root span) and exports
  Perfetto-loadable Chrome trace JSON;
* the flight recorder stays bounded under a request flood;
* instrumentation never changes results: a traced (and profiled)
  request is bit-identical to one served with observability disabled,
  and the ``profile`` field never enters ``engine_key``/``batch_key``.
"""

import json
import threading

import numpy as np
import pytest

from repro.serving.cache import ExecutableCache
from repro.serving.client import ForecastClient
from repro.serving.observability import (FlightRecorder, Observability,
                                         ObservabilityConfig)
from repro.serving.scheduler import (ForecastScheduler, ModelPool,
                                     RequestSpec)
from repro.serving.service import ForecastService
from repro.telemetry import (NULL_TRACE, MetricsRegistry, RequestTrace,
                             parse_prometheus, prom_value)

SPEC = RequestSpec(config="smoke", members=2, lead_steps=3, lead_chunk=2,
                   scored=True, return_state=True)


@pytest.fixture(scope="module")
def pool():
    return ModelPool()


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("traces")


@pytest.fixture(scope="module")
def sched(pool, trace_dir):
    s = ForecastScheduler(
        pool=pool, cache=ExecutableCache(), max_concurrency=1,
        observability=ObservabilityConfig(trace_dir=str(trace_dir)))
    yield s
    s.close()


class TestMetricsPrimitives:
    """repro.telemetry: counters/gauges/histograms and the registry."""

    def test_counter_labels_and_values(self):
        reg = MetricsRegistry()
        c = reg.counter("x_requests_total", "help", ("priority",))
        c.inc(priority="batch")
        c.inc(2, priority="interactive")
        assert c.value(priority="batch") == 1.0
        assert c.value(priority="interactive") == 2.0
        assert c.value(priority="nope") == 0.0
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1, priority="batch")
        with pytest.raises(ValueError, match="label"):
            c.inc(wrong="batch")

    def test_gauge_can_move_both_ways(self):
        reg = MetricsRegistry()
        g = reg.gauge("x_depth", "help")
        g.set(5)
        g.inc(-2)
        assert g.value() == 3.0

    def test_histogram_buckets_cumulative(self):
        reg = MetricsRegistry()
        h = reg.histogram("x_seconds", "help", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        parsed = parse_prometheus(reg.prometheus_text())
        assert prom_value(parsed, "x_seconds_bucket", le="0.1") == 1.0
        assert prom_value(parsed, "x_seconds_bucket", le="1") == 2.0
        assert prom_value(parsed, "x_seconds_bucket", le="+Inf") == 3.0
        assert prom_value(parsed, "x_seconds_count") == 3.0
        assert prom_value(parsed, "x_seconds_sum") == pytest.approx(5.55)

    def test_registry_idempotent_and_type_checked(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", "help")
        assert reg.counter("x_total", "help") is a
        with pytest.raises(ValueError, match="x_total"):
            reg.gauge("x_total", "help")

    def test_prometheus_text_parse_round_trip_with_escapes(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total", "help", ("path",))
        nasty = 'a"b\\c\nd'
        c.inc(3, path=nasty)
        parsed = parse_prometheus(reg.prometheus_text())
        assert prom_value(parsed, "x_total", path=nasty) == 3.0

    def test_collector_callback_scraped_live(self):
        reg = MetricsRegistry()
        state = {"n": 1}
        reg.register_collector(lambda: [{
            "name": "x_live", "type": "gauge", "help": "h",
            "samples": [({}, float(state["n"]))]}])
        assert prom_value(parse_prometheus(reg.prometheus_text()),
                          "x_live") == 1.0
        state["n"] = 7
        assert prom_value(parse_prometheus(reg.prometheus_text()),
                          "x_live") == 7.0


class TestRequestTrace:
    """Span trees: nesting, durations, Chrome export, null object."""

    def test_nesting_and_tree(self):
        tr = RequestTrace("r1", {"k": "v"}, t0=100.0)
        a = tr.add("queue", 100.0, 101.0)
        roll = tr.add("rollout", 101.0, 103.5)
        tr.add("dispatch", 101.0, 102.0, parent=roll, args={"index": 0})
        tr.add("dispatch", 102.0, 103.5, parent=roll, args={"index": 1})
        live = tr.begin("stream")  # begin/end pair uses the real clock
        tr.end(live)
        tr.finish()
        assert a > 0 and tr.finished
        tree = tr.tree()
        assert tree["name"] == "request"
        kids = {c["name"]: c for c in tree["children"]}
        assert set(kids) == {"queue", "rollout", "stream"}
        chunks = kids["rollout"]["children"]
        assert [(c["name"], c["args"]["index"]) for c in chunks] == [
            ("dispatch", 0), ("dispatch", 1)]
        # child durations sum to exactly their parent's (contiguous)
        assert sum(c["dur_s"] for c in chunks) == \
            pytest.approx(kids["rollout"]["dur_s"])
        assert kids["rollout"]["dur_s"] == pytest.approx(2.5)

    def test_chrome_export_shape(self):
        tr = RequestTrace("r2", t0=10.0)
        sid = tr.add("queue", 10.0, 10.5)
        tr.finish()
        ch = tr.to_chrome()
        assert ch["displayTimeUnit"] == "ms"
        xs = [e for e in ch["traceEvents"] if e["ph"] == "X"]
        metas = [e for e in ch["traceEvents"] if e["ph"] == "M"]
        assert metas, "expected process/thread metadata events"
        q = next(e for e in xs if e["name"] == "queue")
        assert q["ts"] == 0 and q["dur"] == 500_000  # us, relative to t0
        assert q["args"]["span_id"] == sid
        # round-trips through json (Perfetto loads a plain dump)
        json.loads(json.dumps(ch))

    def test_null_trace_is_inert(self):
        assert NULL_TRACE.begin("x") == 0
        NULL_TRACE.add("x", 0.0, 1.0)
        NULL_TRACE.end(0)
        with NULL_TRACE.span("x") as sid:
            assert sid == 0
        NULL_TRACE.finish()
        assert NULL_TRACE.to_chrome()["traceEvents"] == []

    def test_trace_ring_bounded(self):
        obs = Observability(ObservabilityConfig(trace_capacity=2))
        for i in range(3):
            obs.finish_trace(obs.begin_trace(f"r{i}"))
        assert obs.trace_json("r0") is None  # evicted
        assert obs.trace_json("r2") is not None
        assert obs.metrics is not None
        assert int(obs.traces.value()) == 3


class TestFlightRecorder:
    def test_bounded_under_flood(self):
        fr = FlightRecorder(capacity=16, max_events=8)
        for i in range(10_000):
            fr.start(f"r{i}")
            fr.record(f"r{i}", "submitted")
        snap = fr.snapshot()
        assert len(snap["active"]) <= 16
        assert len(snap["finished"]) <= 16
        assert all(e["outcome"] == "evicted" for e in snap["finished"])

    def test_per_entry_event_bound(self):
        fr = FlightRecorder(capacity=4, max_events=8)
        fr.start("r0", {"members": 2})
        for _ in range(100):
            fr.record("r0", "tick")
        fr.finish("r0", "done")
        entry = fr.snapshot()["finished"][-1]
        assert len(entry["events"]) == 8
        assert entry["dropped"] == 92
        assert entry["spec"] == {"members": 2}

    def test_unknown_request_is_noop(self):
        fr = FlightRecorder()
        fr.record("ghost", "tick")
        fr.finish("ghost", "done")
        assert fr.snapshot()["finished"] == []


class TestServedTraces:
    """A real served request produces a gap-free, exported span tree."""

    @pytest.fixture(scope="class")
    def served(self, sched):
        res = sched.submit(SPEC).result()
        return res

    def test_span_taxonomy_covered(self, sched, served):
        trace = sched.trace_json(served.request_id)
        names = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "X"}
        required = {"request", "admit", "queue", "coalesce",
                    "engine_build", "inputs", "rollout", "dispatch",
                    "score_fetch", "encode", "finalize"}
        assert required <= names, names
        assert "compile" in names or "aot_hit" in names

    def test_no_gaps_over_root(self, sched, served):
        trace = sched.trace_json(served.request_id)
        xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        root = next(e for e in xs if e["name"] == "request")
        ivals = sorted((e["ts"], e["ts"] + e["dur"]) for e in xs
                       if e is not root)
        covered, edge = 0, root["ts"]
        for a, b in ivals:
            a = max(a, edge)
            if b > a:
                covered += b - a
                edge = b
        assert covered >= 0.95 * root["dur"], \
            f"covered {covered}us of {root['dur']}us"

    def test_trace_dumped_to_disk(self, sched, served, trace_dir):
        path = trace_dir / f"{served.request_id}.trace.json"
        assert path.exists()
        on_disk = json.loads(path.read_text())
        assert on_disk["displayTimeUnit"] == "ms"
        assert any(e.get("name") == "rollout"
                   for e in on_disk["traceEvents"])

    def test_flight_recorder_saw_lifecycle(self, sched, served):
        dbg = sched.debug_requests()
        entry = next(e for e in dbg["finished"]
                     if e["request_id"] == served.request_id)
        assert entry["outcome"] == "done"
        events = [ev["event"] for ev in entry["events"]]
        assert events[0] == "submitted" and "picked" in events
        assert events[-1] == "done"


class TestHTTPEndpoints:
    @pytest.fixture(scope="class")
    def server(self, sched):
        svc = ForecastService(scheduler=sched)
        srv = svc.make_server(port=0)
        thread = threading.Thread(target=srv.serve_forever, daemon=True)
        thread.start()
        yield srv
        srv.shutdown()
        srv.server_close()

    @pytest.fixture(scope="class")
    def client(self, server):
        return ForecastClient(port=server.server_address[1])

    def test_metrics_agree_exactly_with_stats(self, sched, client):
        rid = None
        for ev in client.stream(SPEC):
            if ev["event"] == "done":
                rid = ev["request_id"]
        assert rid is not None
        stats = client.stats()
        parsed = parse_prometheus(client.metrics())

        def pv(name, **labels):
            return prom_value(parsed, f"fcn3_serving_{name}", **labels)

        assert pv("requests_served_total") == stats["served"]
        assert pv("requests_failed_total") == stats["failed"]
        for size, n in stats["batches"].items():
            assert pv("batches_total", size=size) == n
        qos = stats["qos"]
        assert pv("batch_shrinks_total") == qos["batch_shrinks"]
        # pool/cache collector exports agree with their stats blocks
        assert pv("engine_pool_engines") == stats["pool"]["engines"]
        assert pv("cache_hits_total") == stats["cache"]["hits"]
        assert pv("cache_misses_total") == stats["cache"]["misses"]

    def test_trace_endpoint_and_404(self, sched, client):
        res = sched.submit(SPEC).result()
        trace = client.trace(res.request_id)
        assert any(e.get("name") == "rollout"
                   for e in trace["traceEvents"])
        from repro.serving import transport
        with pytest.raises(transport.ServingError, match="404"):
            client.trace("nope")

    def test_debug_requests_endpoint(self, client):
        dbg = client.debug_requests()
        assert dbg["enabled"] is True
        assert dbg["finished"], "expected served requests in the ring"
        assert all("events" in e for e in dbg["finished"])


class TestBitIdentity:
    """Instrumentation must never change results."""

    @pytest.fixture(scope="class")
    def dark(self, pool):
        """A scheduler with observability fully disabled."""
        s = ForecastScheduler(
            pool=pool, cache=ExecutableCache(), max_concurrency=1,
            observability=ObservabilityConfig(enabled=False))
        yield s
        s.close()

    def test_disabled_path_uses_null_trace(self, dark):
        res = dark.submit(SPEC).result()
        assert dark.trace_json(res.request_id) is None
        assert dark.debug_requests()["finished"] == []

    def test_traced_bit_identical_to_untraced(self, sched, dark):
        traced = sched.submit(SPEC).result()
        plain = dark.submit(SPEC).result()
        for name in traced.scores:
            np.testing.assert_array_equal(traced.scores[name],
                                          plain.scores[name],
                                          err_msg=name)
        np.testing.assert_array_equal(traced.final_state,
                                      plain.final_state)

    def test_profiled_bit_identical(self, pool, dark, tmp_path):
        prof = ForecastScheduler(
            pool=pool, cache=ExecutableCache(), max_concurrency=1,
            observability=ObservabilityConfig(
                profile_dir=str(tmp_path / "xla")))
        try:
            spec = RequestSpec(**{**SPEC.to_dict(), "profile": True})
            res = prof.submit(spec).result()
            plain = dark.submit(SPEC).result()
            for name in res.scores:
                np.testing.assert_array_equal(res.scores[name],
                                              plain.scores[name],
                                              err_msg=name)
            np.testing.assert_array_equal(res.final_state,
                                          plain.final_state)
        finally:
            prof.close()

    def test_profile_field_never_in_dispatch_keys(self):
        on = RequestSpec(**{**SPEC.to_dict(), "profile": True})
        off = RequestSpec(**{**SPEC.to_dict(), "profile": False})
        assert on.engine_key() == off.engine_key()
        assert on.batch_key() == off.batch_key()
        assert on.engine_config() == off.engine_config()
        # ...but it round-trips the wire format
        assert RequestSpec.from_dict(on.to_dict()).profile is True
