"""The reduction from a profiler trace to device metrics."""

import os

import jax
import jax.numpy as jnp
import pytest

from bench_helpers import ROOT, run
from bench import device_metrics, trace as tr
from bench.peaks import peaks_for
from bench.work import hvp_work, roofline_s

OPS = [("fusion", 0, 10), ("chess_hvp", 5, 15), ("chess_hvp", 20, 30)]


def test_busy_is_the_union_of_op_intervals():
    assert tr.merge([(o[1], o[2]) for o in OPS]) == [(0, 15), (20, 30)]
    assert tr.busy_ns(OPS, 0, 40) == 25
    assert tr.busy_ns(OPS, 10, 25) == 10


def test_gaps_and_idle_share():
    assert tr.gaps(OPS, 0, 40) == [(15, 20), (30, 40)]
    assert tr.gaps(OPS, -5, 30) == [(-5, 0), (15, 20)]
    view = run.RunView(None, None, {}, tr.Timeline(ops={"/device:TPU:0":
                                                         OPS}), (0, 40),
                       "TPU v5 lite")
    assert device_metrics.idle_pct(view) == pytest.approx(37.5)


def test_idle_share_is_averaged_over_devices():
    tl = tr.Timeline(ops={"/device:TPU:0": OPS,
                          "/device:TPU:1": [("x", 0, 40)]})
    view = run.RunView(None, None, {}, tl, (0, 40), "TPU v5 lite")
    assert device_metrics.idle_pct(view) == pytest.approx(37.5 / 2)


def test_no_device_in_the_trace_reads_nothing():
    view = run.RunView(None, None, {}, tr.Timeline(), (0, 40), "cpu")
    assert device_metrics.idle_pct(view) is None
    assert device_metrics.roofline_pct(view, "bench:batched_hvp", 16,
                                       64) is None


def test_top_ops_sums_by_name():
    assert tr.top_ops(OPS) == [("chess_hvp", 20e-9), ("fusion", 10e-9)]
    assert tr.top_ops(OPS, k=1) == [("chess_hvp", 20e-9)]


def test_ops_within_call_spans_are_cut_to_them():
    assert tr.within(OPS, [(8, 22)]) == [("fusion", 8, 10),
                                         ("chess_hvp", 8, 15),
                                         ("chess_hvp", 20, 22)]


def test_gaps_are_named_by_the_host_span_open_in_them():
    host = [("bench:window", 0, 40), ("bench:batched_hvp", 0, 18),
            ("PjitFunction(traced)", 16, 19), ("ThunkExecutor", 31, 45)]
    # gap (15, 20): bench:batched_hvp is the newest annotation open at 17.5
    # gap (30, 40): only bench:window (an annotation) and a runtime event
    assert tr.host_spans_at(host, [17.5, 35.0, 50.0]) == [
        "bench:batched_hvp", "bench:window", "no host span"]
    assert tr.host_spans_at([("PjitFunction(traced)", 16, 19)],
                            [17.0]) == ["PjitFunction(traced)"]
    named = tr.name_gaps(OPS, host, 0, 40)
    assert named == [("bench:window", 10e-9), ("bench:batched_hvp", 5e-9)]


def test_roofline_share_on_a_synthetic_trace():
    m, n = 64, 16
    config = run.load_config(run.load_benchmark(), "rosenbrock")
    calls = [(0, 1_000_000), (2_000_000, 3_000_000)]
    ops = [("chess_hvp", 100, 900_100), ("copy", 2_000_000, 2_500_000)]
    tl = tr.Timeline(ops={"/device:TPU:0": ops},
                     host=[("bench:batched_hvp", a, b) for a, b in calls])
    view = run.RunView(None, config, {}, tl, (0, 3_000_000), "TPU v5 lite")
    got = device_metrics.roofline_pct(view, "bench:batched_hvp", n, m)
    least_s, bound = roofline_s(hvp_work(config.module.formula(n), m, n),
                                peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert got["bound"] == "memory" and got["calls"] == 2
    assert got["device_s"] == pytest.approx(1.4e-3)
    assert got["value"] == pytest.approx(100 * 2 * least_s / 1.4e-3)


def test_hvp_roofline_counts_calls_that_run_after_their_dispatch():
    """Calls dispatched ahead run after the short host span that sent them:
    the reader takes the device time of the whole window and the window's
    count of calls."""
    m, n = 64, 16
    config = run.load_config(run.load_benchmark(), "rosenbrock")
    config.spec = {**config.spec, "m": m}
    ops = [("chess_hvp", 100, 900_100), ("copy", 2_000_000, 2_500_000)]
    host = [("bench:window", 0, 3_000_000), ("bench:batched_hvp", 0, 50),
            ("bench:batched_hvp", 60, 90)]
    view = run.RunView(None, config, {"calls": 2},
                       tr.Timeline(ops={"/device:TPU:0": ops}, host=host),
                       (0, 3_000_000), "TPU v5 lite")
    got = run.load_reader("hvp_roofline")(view)
    least_s, _ = roofline_s(hvp_work(config.module.formula(n), m, n),
                            peaks_for("TPU v5 lite"))
    assert got["calls"] == 2
    assert got["device_s"] == pytest.approx(1.4e-3)
    assert got["value"] == pytest.approx(100 * 2 * least_s / 1.4e-3)


def test_load_reads_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            with jax.profiler.TraceAnnotation("bench:batched_hvp"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    tl = tr.load(str(tmp_path))
    (w0, w1), = tl.spans_named("bench:window")
    (c0, c1), = tl.spans_named("bench:batched_hvp")
    assert w0 <= c0 < c1 <= w1
    # the CPU backend has no device plane: nothing for a device reader
    assert tl.ops == {}


def test_load_without_a_trace_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.load(os.path.join(str(tmp_path), "none"))


def test_root_is_the_checkout():
    assert os.path.isfile(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("name,chip", [
    ("/device:TPU:0", True), ("/device:TPU:3", True),
    ("/device:TPU:0 SparseCore 0", False), ("/device:CPU:0", False),
    ("/host:CPU", False)])
def test_device_planes_are_whole_chips(name, chip):
    assert tr._is_device_plane(name) is chip
