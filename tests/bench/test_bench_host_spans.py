"""The per-layer readers of the program's ``repro:`` host spans, on
synthetic timelines and on a served round trip traced on the CPU."""

import time

import jax
import numpy as np
import pytest

from bench_helpers import run, run_small
from bench import host_spans as hs, trace as tr

US = 1_000          # ns per microsecond
WINDOW = (0, 1_000 * US)
# two buckets of one dispatch worker, then its waits; reply runs inside
# respond, as the frontend's done-callback does
WORKER_SPANS = [
    ("coalesce", 0, 10), ("marshal", 10, 30), ("device_execute", 30, 60),
    ("readback", 60, 70), ("respond", 70, 100), ("reply", 75, 95),
    ("worker_wait", 100, 200),
    ("coalesce", 200, 210), ("marshal", 210, 240),
    ("device_execute", 240, 300), ("readback", 300, 310),
    ("respond", 310, 350), ("reply", 320, 340),
    ("worker_wait", 360, 1_000)]
READER_SPANS = [("decode", -2, 3), ("submit", 3, 8), ("decode", 100, 107),
                ("submit", 107, 111), ("client_send", 500, 502),
                ("reply", 1_100, 1_200), ("decode", 1_100, 1_150)]
DEVICE_OPS = [("hvp", 40 * US, 50 * US), ("hvp", 250 * US, 260 * US)]
COUNTERS = {"dispatched": 8, "batches": 2}


def _view(spans, ops=DEVICE_OPS, counters=COUNTERS):
    host = [("bench:window", *WINDOW)] + [
        (hs.PREFIX + name, t0 * US, t1 * US) for name, t0, t1 in spans]
    tl = tr.Timeline(ops={"/device:TPU:0": list(ops)} if ops else {},
                     host=host)
    return run.RunView(None, None, dict(counters), tl, WINDOW,
                       "TPU v5 lite")


def _reader(name):
    return run.load_reader(name)


def test_dispatch_host_time_per_row():
    got = _reader("dispatch_host_us_per_row")(_view(WORKER_SPANS))
    # 2 buckets x 4 rows per bucket; coalesce 20 us, marshal 50, readback
    # 20, respond 70 (the nested reply is not added again)
    assert got["rows"] == 8 and got["buckets"] == 2
    assert got["coalesce_us"] == pytest.approx(2.5)
    assert got["marshal_us"] == pytest.approx(6.25)
    assert got["readback_us"] == pytest.approx(2.5)
    assert got["respond_us"] == pytest.approx(8.75)
    assert got["device_execute_us"] == pytest.approx(90 / 8)
    assert got["value"] == pytest.approx(20.0)
    assert got["value"] == pytest.approx(sum(
        got[f"{s}_us"] for s in ("coalesce", "marshal", "readback",
                                 "respond")))
    assert got["worker_busy_pct"] == pytest.approx(25.0)
    assert got["worker_wait_pct"] == pytest.approx(74.0)


def test_nested_reply_is_not_counted_twice():
    without = [s for s in WORKER_SPANS if s[0] != "reply"]
    a = _reader("dispatch_host_us_per_row")(_view(WORKER_SPANS))
    b = _reader("dispatch_host_us_per_row")(_view(without))
    assert a["value"] == pytest.approx(b["value"])
    assert a["idle_s_by_stage"] == pytest.approx(b["idle_s_by_stage"])


def test_idle_time_is_split_by_interval_intersection():
    got = _reader("dispatch_host_us_per_row")(_view(WORKER_SPANS))
    idle = got["idle_s_by_stage"]
    # gaps (0, 40), (50, 250), (260, 1000) us cut by each stage's spans
    want = {"coalesce": 20, "marshal": 50, "device_execute": 70,
            "readback": 20, "respond": 70, "worker_wait": 740,
            "no worker span": 10}
    assert idle == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    # the parts add up to the idle time, where the midpoint rule books
    # each whole gap to one stage
    assert sum(idle.values()) == pytest.approx(980e-6)
    mid = dict(tr.name_gaps(DEVICE_OPS, _view(WORKER_SPANS).timeline.host,
                            *WINDOW))
    assert mid == pytest.approx({"repro:marshal": 40e-6,
                                 "repro:worker_wait": 940e-6})


def test_overlap_of_interval_sets():
    assert hs.overlap_ns([(0, 10), (5, 20)], [(15, 30)]) == 5
    assert hs.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert hs.overlap_ns([], [(0, 1)]) == 0


def test_transport_time_per_request():
    got = _reader("transport_us_per_request")(
        _view(WORKER_SPANS + READER_SPANS))
    # replies answered in the window: 2; decode clipped to it: 3 + 7 us
    assert got["requests"] == 2
    assert got["decode_us"] == pytest.approx(5.0)
    assert got["reply_us"] == pytest.approx(20.0)
    assert got["submit_us"] == pytest.approx(4.5)
    assert got["client_send_us"] == pytest.approx(1.0)
    assert got["client_recv_us"] == 0.0
    assert got["value"] == pytest.approx(25.0)


@pytest.mark.parametrize("reader", ["dispatch_host_us_per_row",
                                    "transport_us_per_request"])
def test_readers_read_nothing_without_their_spans(reader):
    assert _reader(reader)(_view([])) is None
    view = _view([])
    view.timeline = None
    assert _reader(reader)(view) is None


def test_dispatch_reader_needs_the_bucket_counters():
    assert _reader("dispatch_host_us_per_row")(
        _view(WORKER_SPANS, counters={})) is None
    # no device plane (the CPU): no idle split, the rest is read
    got = _reader("dispatch_host_us_per_row")(_view(WORKER_SPANS, ops=()))
    assert got["idle_s_by_stage"] is None and got["value"] > 0


STAGES = ("decode", "submit", "coalesce", "marshal", "device_execute",
          "readback", "respond", "reply", "worker_wait", "client_send",
          "client_recv")


def test_a_served_round_trip_under_a_capture_names_every_stage(tmp_path):
    from repro import engine
    from repro.core import testfns
    from repro.serving.frontend import CurvatureFrontend, connect
    fam = testfns.ragged_family("rosenbrock")
    plans = {"rosenbrock": lambda n: engine.plan(fam, n, symmetric=False)}
    rng = np.random.default_rng(0)
    with CurvatureFrontend(plans, max_batch=4, max_wait_us=200.0) as fe:
        with connect(*fe.address, client="t") as cli:
            a, v = rng.standard_normal((2, 8)).astype(np.float32)
            cli.hvp("rosenbrock", a, v)         # compiled outside
            jax.profiler.start_trace(str(tmp_path))
            try:
                with jax.profiler.TraceAnnotation("bench:window"):
                    for _ in range(3):
                        cli.hvp("rosenbrock", a, v)
                    time.sleep(0.01)
            finally:
                jax.profiler.stop_trace()
    tl = tr.load(str(tmp_path))
    named = {n[len(hs.PREFIX):] for n, _t0, _t1 in tl.host
             if n.startswith(hs.PREFIX)}
    assert set(STAGES) <= named, set(STAGES) - named
    (w0, w1), = tl.spans_named("bench:window")
    assert len(hs.spans(tl, "reply", w0, w1)) == 3


def test_a_traced_served_run_reports_both_readers():
    res = run_small("rosenbrock.served-closed", seed=2 ** 33 + 5,
                    trace=True)
    assert res["correct"] is True
    d = res["metrics"]["dispatch_host_us_per_row"]
    t = res["metrics"]["transport_us_per_request"]
    assert d["value"] > 0 and t["value"] > 0
    assert d["value"] == pytest.approx(sum(
        d[f"{s}_us"] for s in ("coalesce", "marshal", "readback",
                               "respond")))
    assert t["value"] == pytest.approx(t["decode_us"] + t["reply_us"])
