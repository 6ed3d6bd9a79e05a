"""Device metrics shared by per-layer readers, from a run's Timeline."""

from __future__ import annotations

from . import trace as tr
from .peaks import peaks_for
from .work import Work, hvp_work, roofline_s

__all__ = ["idle_pct", "share_of_roofline", "roofline_pct", "peak_share_pct"]


def idle_pct(run):
    """100 x (1 - busy / window), averaged over the devices; None where the
    trace holds no device."""
    if run.timeline is None or not run.timeline.ops:
        return None
    lo, hi = run.window_ns
    busy = [tr.busy_ns(ops, lo, hi) for ops in run.timeline.ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))


def share_of_roofline(run, span: str, work: Work, calls: int | None = None):
    """The roofline time of ``work`` per call, over the device time of
    every operation inside the host spans named ``span``, in %; None where
    there is no such span or no device time.  One span per call, unless
    ``calls`` counts the calls that the spans hold: work dispatched ahead
    runs after the span that sent it, so its reader passes the window's
    span and the count of calls in it."""
    if run.timeline is None:
        return None
    spans = run.timeline.spans_named(span)
    ops = run.timeline.all_ops()
    if not spans or not ops:
        return None
    device_ns = tr.busy_ns(tr.within(ops, spans), spans[0][0], spans[-1][1])
    if device_ns <= 0:
        return None
    calls = len(spans) if calls is None else calls
    least_s, bound = roofline_s(work, peaks_for(run.device_kind))
    return {"value": 100.0 * calls * least_s / (device_ns * 1e-9),
            "bound": bound, "calls": calls, "device_s": device_ns * 1e-9}


def roofline_pct(run, span: str, n: int, m: int, calls: int | None = None):
    """``share_of_roofline`` of a batched HVP at (m, n) of the
    configuration's formula (``bench.work.hvp_work``)."""
    if run.timeline is None or not run.timeline.spans_named(span):
        return None
    return share_of_roofline(run, span, hvp_work(run.config.module.formula(n),
                                                 m, n), calls)


def peak_share_pct(run, span: str, flops_per_call: float):
    """The whole step's share of the chip's peak, in %: the operations of
    the calls under host span ``span`` over their wall time at the peak
    FLOP/s of ``bench/peaks.py``,
    100 x calls x flops_per_call / (wall time x peak).  None where there is
    no such call."""
    if run.timeline is None:
        return None
    calls = run.timeline.spans_named(span)
    wall_ns = sum(t1 - t0 for t0, t1 in calls)
    if not calls or wall_ns <= 0:
        return None
    peak = peaks_for(run.device_kind).flops
    return {"value": 100.0 * len(calls) * flops_per_call
            / (wall_ns * 1e-9 * peak),
            "calls": len(calls), "wall_s": wall_ns * 1e-9}
