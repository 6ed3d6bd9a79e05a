"""Observability subsystem acceptance (PR 10).

Three layers of witness:

  * **unit** -- the metrics registry (counters/gauges/histograms, labels,
    both exporters, scrape-time collectors) and the trace/flight-recorder
    pillar, all under injected clocks so timing is deterministic;
  * **parity** -- ``service.stats()`` and the metrics registry must agree
    on every shared counter.  After the collector refactor this is true
    BY CONSTRUCTION (the registry series are scrape-time views over the
    same stats dict), and this test is the regression tripwire that keeps
    it that way;
  * **end-to-end** -- a TCP client drives a frontend with admission
    configured and reads back traces whose spans cover the whole path
    (admit -> enqueue -> coalesce -> dispatch_wait -> marshal ->
    device_execute -> readback -> respond) plus metrics in both wire
    formats;
  * **profiler** -- any capture lights the ``repro:`` annotations, and
    none is constructed outside one.
"""

import json

import numpy as np
import pytest

from repro import engine, obs
from repro.core import testfns
from repro.engine.service import CurvatureService
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import FlightRecorder, Trace
from repro.serving import AdmissionController, ClientPolicy

NS = (8, 12, 16)


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts from an enabled, empty registry/recorder and
    restores the process default on the way out."""
    was = obs.enabled()
    obs.enable()
    obs.reset()
    yield
    obs.set_enabled(was)
    obs.reset()


def _xv(n, seed=0):
    rng = np.random.RandomState(seed)
    return (np.asarray(rng.uniform(-2, 2, n), np.float32),
            np.asarray(rng.randn(n), np.float32))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    t = [0.0]
    reg = MetricsRegistry(clock=lambda: t[0], time_scale=1e6)
    c = reg.counter("reqs_total", "requests", labelnames=("priority",))
    c.inc(priority="batch")
    c.inc(2.0, priority="interactive")
    assert c.value(priority="batch") == 1.0
    assert c.total() == 3.0
    g = reg.gauge("depth", "queue depth")
    g.set(7.0)
    g.dec(2.0)
    assert g.value() == 5.0
    h = reg.histogram("lat_us", "latency", buckets=(10.0, 100.0, 1000.0))
    h.observe(50.0)
    h.observe(5000.0)                       # lands in +Inf
    with h.time():                          # injected clock: exactly 100us
        t[0] += 100e-6
    snap = h.snapshot()
    assert snap["count"] == 3
    assert snap["counts"] == [0, 2, 0, 1]   # 50+100 share (10,100]
    assert snap["sum"] == pytest.approx(5150.0)


def test_metric_declarations_are_idempotent_but_conflicts_raise():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", labelnames=("k",))
    assert reg.counter("x_total", labelnames=("k",)) is c1
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")                # kind conflict
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labelnames=("other",))
    with pytest.raises(ValueError, match="labelnames"):
        c1.inc(wrong="v")                   # undeclared label


def test_exporters_emit_both_formats():
    reg = MetricsRegistry()
    reg.counter("a_total", "things", labelnames=("kind",)).inc(kind="x")
    reg.histogram("d_us", "durations", buckets=(10.0, 100.0)).observe(42.0)
    text = reg.to_prometheus()
    assert "# TYPE a_total counter" in text
    assert 'a_total{kind="x"} 1' in text
    assert 'd_us_bucket{le="100"} 1' in text
    assert 'd_us_bucket{le="+Inf"} 1' in text
    assert "d_us_count 1" in text
    j = reg.to_json()
    json.dumps(j)                           # JSON-safe end to end
    assert j["a_total"]["type"] == "counter"
    assert j["d_us"]["series"][0]["buckets"]["+Inf"] == 1


def test_collectors_run_at_scrape_time_and_survive_reset():
    reg = MetricsRegistry()
    live = {"pending": 3}                   # stand-in for engine telemetry
    calls = []

    def collect(r):
        calls.append(1)
        r.gauge("pending", "live view").child().set(live["pending"])

    reg.set_collector("svc", collect)
    assert reg.value("pending") == 3.0      # value() scrapes
    live["pending"] = 9
    assert reg.value("pending") == 9.0      # a view, not a copy
    reg.reset()                             # metrics gone, wiring kept
    assert reg.get("pending") is None
    assert reg.value("pending") == 9.0      # repopulated by the collector
    n = len(calls)
    reg.remove_collector("svc")
    reg.to_prometheus()
    assert len(calls) == n                  # removed => no longer invoked


# ---------------------------------------------------------------------------
# tracing + flight recorder
# ---------------------------------------------------------------------------

def _fake_trace(rec, t, spans):
    tr = Trace(meta={"n": 8}, clock=lambda: t[0], recorder=rec)
    for name, dur in spans:
        t0 = t[0]
        t[0] += dur
        tr.add_span(name, t0, t[0])
    tr.finish()
    return tr


def test_recorder_digest_feeds_span_histograms_and_trace_count():
    reg = MetricsRegistry()
    rec = FlightRecorder(registry=reg)
    t = [0.0]
    _fake_trace(rec, t, [("enqueue", 100e-6), ("device_execute", 2e-3)])
    _fake_trace(rec, t, [("enqueue", 200e-6)])
    # record() defers: nothing lands in the registry until digest()
    assert reg.get("repro_span_duration_us") is None
    rec.digest()
    h = reg.get("repro_span_duration_us")
    snap = h.snapshot(span="enqueue")
    assert snap["count"] == 2
    assert snap["sum"] == pytest.approx(300.0)
    assert h.snapshot(span="device_execute")["count"] == 1
    assert reg.value("repro_traces_total") == 2.0
    rec.digest()                            # idempotent when drained
    assert reg.value("repro_traces_total") == 2.0


def test_recorder_rings_are_bounded_and_slow_traces_survive():
    rec = FlightRecorder(capacity=4, slow_threshold_s=0.05,
                         registry=MetricsRegistry())
    t = [0.0]
    slow = _fake_trace(rec, t, [("device_execute", 0.2)])
    for _ in range(6):                      # fast traffic rotates the ring
        _fake_trace(rec, t, [("device_execute", 1e-4)])
    assert len(rec) == 4
    recents = rec.recent(16)
    assert slow not in recents              # rotated out of recent...
    assert rec.slowest(1)[0] is slow        # ...but kept by the slow ring
    assert rec.slowest(1)[0].duration_s == pytest.approx(0.2)
    rec.clear()
    assert len(rec) == 0 and rec.slowest(3) == []


def test_trace_span_context_and_to_dict_are_json_safe():
    t = [1.0]
    rec = FlightRecorder(registry=MetricsRegistry())
    tr = Trace(meta={"client": "c", "arr": np.float32(2.5)},
               clock=lambda: t[0], recorder=rec)
    with tr.span("admit"):
        t[0] += 0.001
    tr.add_span("device_execute", t[0], t[0] + 0.002,
                meta={"bucket": 4, "n_pad": np.int64(16)})
    tr.finish(error="Boom")
    d = tr.to_dict()
    json.dumps(d)                           # numpy leaked nowhere
    assert d["meta"]["error"] == "Boom"
    assert [s["name"] for s in d["spans"]] == ["admit", "device_execute"]
    assert d["spans"][0]["dur_ms"] == pytest.approx(1.0)
    assert d["spans"][1]["meta"]["bucket"] == 4
    tr.finish()                             # idempotent
    assert len(rec) == 1


def test_disabled_obs_is_inert():
    obs.disable()
    assert obs.trace_begin(client="x") is None
    assert obs.event("retune", plan="p") is None
    obs.enable()
    assert isinstance(obs.trace_begin(), Trace)
    assert obs.event("retune", plan="p")["kind"] == "retune"


# ---------------------------------------------------------------------------
# parity: stats() and the registry agree by construction (satellite d)
# ---------------------------------------------------------------------------

def test_service_stats_and_metrics_registry_agree():
    """Every counter the service exposes through BOTH surfaces must
    match exactly: the registry series are scrape-time views over the
    same telemetry the stats() dict snapshots."""
    engine.clear_telemetry()
    fam = testfns.ragged_family("rosenbrock")
    plans = {n: engine.plan(fam, n, symmetric=False) for n in NS}
    svc = CurvatureService(max_batch=4, max_wait_us=100.0, start=False,
                           coalesce_across_n=True)
    futs = []
    for i, n in enumerate(list(NS) * 3):
        a, v = _xv(n, seed=i)
        futs.append(svc.submit(plans[n], a, v, client=f"c{i % 2}",
                               priority="interactive" if i % 3 else "batch"))
    svc.flush()
    for f in futs:
        f.result(timeout=30)
    s = svc.stats()
    reg = obs.metrics_registry()
    assert reg.total("repro_requests_total") == s["submitted"]
    assert reg.value("repro_requests_total", priority="batch") == 3.0
    assert reg.total("repro_points_total") == s["dispatched"]
    assert reg.value("repro_batches_total", kind="ragged") == \
        s["ragged_batches"]
    assert reg.value("repro_batches_total", kind="dense") == \
        s["batches"] - s["ragged_batches"]
    assert reg.total("repro_padded_rows_total") == s["padded_rows"]
    assert reg.total("repro_cross_n_fills_total") == s["cross_n_fills"]
    for b, count in s["buckets"].items():
        assert reg.value("repro_bucket_batches_total", bucket=b) == count
    assert reg.value("repro_pending") == 0.0
    assert reg.total("repro_traces_total") == s["submitted"]
    # per-client views mirror engine.client_stats()
    for cid, tot in engine.client_stats().items():
        assert reg.value("repro_client_points_total", client=cid) == \
            tot["points"]
    svc.shutdown()
    # shutdown retires the collector after one final scrape: the frozen
    # values remain readable and no stale callback fires on future scrapes
    assert reg.total("repro_points_total") == s["dispatched"]


def test_admission_shed_counts_agree_with_registry():
    adm = AdmissionController(default_policy=ClientPolicy(rate=0.001,
                                                          burst=1))
    p = engine.plan(testfns.rosenbrock, 8, csize=2, symmetric=False)
    a, v = _xv(8)
    with CurvatureService(max_batch=8, max_wait_us=100.0, start=False,
                          admission=adm) as svc:
        fut = svc.submit(p, a, v, client="c")       # burst token
        with pytest.raises(Exception):              # ServiceOverloaded
            svc.submit(p, a, v, client="c")
        svc.flush()
        fut.result(timeout=30)
        reg = obs.metrics_registry()
        assert reg.value("repro_admission_shed_total", reason="rate") == \
            svc.stats()["admission"]["shed_rate"] == 1
        # the shed submit's trace is sealed with the error recorded
        shed = [t for t in obs.recorder().recent(16)
                if t.meta.get("error")]
        assert shed and shed[0].meta["error"] == "ServiceOverloaded"


def test_executions_histogram_feeds_per_point_cost():
    p = engine.plan(testfns.rosenbrock, 8, csize=2, symmetric=False)
    a, v = _xv(8)
    with CurvatureService(max_batch=8, max_wait_us=100.0,
                          start=False) as svc:
        fut = svc.submit(p, a, v)
        svc.flush()
        fut.result(timeout=30)
    reg = obs.metrics_registry()
    assert reg.total("repro_executions_total") >= 1
    h = reg.get("repro_execution_us_per_point")
    assert h is not None
    (lv, child), *_ = h.series()
    assert child.snapshot()["count"] >= 1


# ---------------------------------------------------------------------------
# end to end: traces + metrics over the wire
# ---------------------------------------------------------------------------

def test_wire_traces_cover_the_full_request_path():
    from repro.serving.frontend import CurvatureFrontend, connect
    fam = testfns.ragged_family("rosenbrock")
    plans = {"rosenbrock": lambda n: engine.plan(fam, n, symmetric=False)}
    adm = AdmissionController(
        default_policy=ClientPolicy(rate=1000.0, burst=100))
    with CurvatureFrontend(plans, max_batch=8, max_wait_us=200.0,
                           admission=adm) as fe:
        host, port = fe.address
        with connect(host, port, client="e2e") as cli:
            a, v = _xv(8, seed=3)
            cli.hvp("rosenbrock", a, v)
            # the trace lands in the recorder after the client sees the
            # result (respond span closes last) -- poll briefly
            traces = []
            for _ in range(100):
                traces = cli.trace(k=8)["traces"]
                if traces:
                    break
            assert traces, "no trace reached the flight recorder"
            tr = traces[0]
            names = [s["name"] for s in tr["spans"]]
            path = ("enqueue", "coalesce", "dispatch_wait", "marshal",
                    "device_execute", "readback", "respond")
            for want in ("admit",) + path:
                assert want in names, f"span {want!r} missing: {names}"
            span = {s["name"]: s for s in tr["spans"]}
            coalesce = span["coalesce"]
            assert coalesce["meta"]["bucket"] >= 1
            assert coalesce["dur_ms"] >= 0
            # the stages follow one another: each ends where the next
            # starts, up to respond (its own clock read per request)
            for a, b in zip(path[:-2], path[1:-1]):
                end = span[a]["start_ms"] + span[a]["dur_ms"]
                assert end == pytest.approx(span[b]["start_ms"],
                                            abs=1e-9), (a, b)
            assert tr["meta"]["client"] == "e2e"
            assert tr["duration_ms"] > 0
            # both metric exporters over the same wire
            j = cli.metrics()
            assert j["repro_points_total"]["series"][0]["value"] >= 1
            text = cli.metrics(format="prometheus")
            assert "# TYPE repro_requests_total counter" in text
            assert "repro_span_duration_us_bucket" in text


def test_wire_slow_ring_and_events():
    from repro.serving.frontend import CurvatureFrontend, connect
    fam = testfns.ragged_family("rosenbrock")
    plans = {"rosenbrock": lambda n: engine.plan(fam, n, symmetric=False)}
    obs.event("retune", plan="rosenbrock", trigger="test")
    with CurvatureFrontend(plans, max_batch=8, max_wait_us=200.0) as fe:
        host, port = fe.address
        with connect(host, port, client="slowpoke") as cli:
            a, v = _xv(8)
            cli.hvp("rosenbrock", a, v)
            for _ in range(100):
                got = cli.trace(k=4, slow=True)
                if got["traces"]:
                    break
            # slowest() ranks whatever is recorded; with one request it
            # must return that request
            assert got["traces"][0]["meta"]["client"] == "slowpoke"
            kinds = [e["kind"] for e in got["events"]]
            assert "retune" in kinds


def test_profile_session_raises_when_the_trace_cannot_start(monkeypatch):
    from jax import profiler as jp
    from repro.obs import profile as prof

    def broken(*a, **k):
        raise RuntimeError("profiler plugin missing")

    monkeypatch.setattr(jp, "start_trace", broken)
    with pytest.raises(RuntimeError, match="plugin missing"):
        with prof.profile_session("unused-dir"):
            pass
    assert not prof.is_active()         # a later session may start


def test_is_active_follows_a_bare_start_trace(tmp_path):
    from jax import profiler as jp
    assert not obs.is_active()
    jp.start_trace(str(tmp_path))
    try:
        assert obs.is_active()
        with pytest.raises(RuntimeError, match="already running"):
            with obs.profile_session(str(tmp_path / "second")):
                pass
    finally:
        jp.stop_trace()
    assert not obs.is_active()


def test_annotations_are_built_only_under_a_capture(monkeypatch, tmp_path):
    from jax import profiler as jp
    from repro.serving.frontend import CurvatureFrontend, connect
    built = []

    class Counting(jp.TraceAnnotation):
        def __init__(self, name, **meta):
            built.append(name)
            super().__init__(name, **meta)

    monkeypatch.setattr(jp, "TraceAnnotation", Counting)
    fam = testfns.ragged_family("rosenbrock")
    plans = {"rosenbrock": lambda n: engine.plan(fam, n, symmetric=False)}
    with CurvatureFrontend(plans, max_batch=4, max_wait_us=200.0) as fe:
        with connect(*fe.address, client="quiet") as cli:
            a, v = _xv(8, seed=5)
            cli.hvp("rosenbrock", a, v)
            cli.hvp("rosenbrock", a, v)
            assert built == []
            with obs.profile_session(str(tmp_path)):
                cli.hvp("rosenbrock", a, v)
    assert {"repro:decode", "repro:device_execute",
            "repro:reply"} <= set(built)
