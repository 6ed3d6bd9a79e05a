"""The work count and the roofline arithmetic, against hand-computed
values."""

import pytest

from bench_helpers import run
from bench.peaks import PEAKS, peaks_for
from bench.work import Work, hvp_work, roofline_s

V5E = peaks_for("TPU v5 lite")


def test_v5e_peaks_are_the_published_ones():
    assert (V5E.flops, V5E.hbm_bytes, V5E.hbm_capacity) == (197e12, 819e9,
                                                            16e9)
    assert "TPU v5e" in V5E.source


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    assert "cpu" not in PEAKS


def test_roofline_takes_the_larger_bound():
    assert roofline_s(Work(flops=1e9, bytes=819e9), V5E) == (1.0, "memory")
    assert roofline_s(Work(flops=197e12, bytes=8.19e9), V5E) == (1.0,
                                                                 "compute")


@pytest.mark.parametrize("config", ["rosenbrock", "fletcher_powell"])
def test_hvp_work_bytes_and_operations(config):
    cfg = run.load_config(run.load_benchmark(), config)
    f = cfg.module.formula(16)
    w1, w2 = hvp_work(f, 1000, 16), hvp_work(f, 2000, 16)
    assert w1.bytes == 3 * 1000 * 16 * 4          # A, V read, result written
    assert w2.flops == 2 * w1.flops               # the same work per point
    per_point = w1.flops / 1000
    # Rosenbrock: a few tens of operations per term over its 15 terms;
    # Fletcher-Powell: four 16x16 mat-vecs (2 x 256 each) and more
    lo, hi = {"rosenbrock": (150, 600), "fletcher_powell": (2048, 8192)}[
        config]
    assert lo <= per_point <= hi


def test_paper_batch_roofline_at_the_pr11_call_time():
    """96 MB at 819 GB/s is 117 us; against PR 11's 2.12 s per call the
    share is 0.0055% -- the memory bound, as the issue's estimate says."""
    cfg = run.load_config(run.load_benchmark(), "rosenbrock")
    least_s, bound = roofline_s(hvp_work(cfg.module.formula(16), 500_000,
                                         16), V5E)
    assert bound == "memory"
    assert least_s == pytest.approx(96e6 / 819e9)
    assert 100 * least_s / 2.12 == pytest.approx(0.005529, rel=1e-3)
