"""Device metrics shared by per-layer readers, from a run's Timeline."""

from __future__ import annotations

from . import trace as tr
from .peaks import peaks_for
from .work import hvp_work, roofline_s

__all__ = ["idle_pct", "roofline_pct"]


def idle_pct(run):
    """100 x (1 - busy / window), averaged over the devices; None where the
    trace holds no device."""
    if run.timeline is None or not run.timeline.ops:
        return None
    lo, hi = run.window_ns
    busy = [tr.busy_ns(ops, lo, hi) for ops in run.timeline.ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))


def roofline_pct(run, span: str, n: int, m: int):
    """The roofline time of the calls under host span ``span`` over the
    device time of every operation inside them, in %; None where there is
    no such call or no device time."""
    if run.timeline is None:
        return None
    calls = run.timeline.spans_named(span)
    ops = run.timeline.all_ops()
    if not calls or not ops:
        return None
    device_ns = tr.busy_ns(tr.within(ops, calls), calls[0][0], calls[-1][1])
    if device_ns <= 0:
        return None
    work = hvp_work(run.config.module.formula(n), m, n)
    least_s, bound = roofline_s(work, peaks_for(run.device_kind))
    return {"value": 100.0 * len(calls) * least_s / (device_ns * 1e-9),
            "bound": bound, "calls": len(calls),
            "device_s": device_ns * 1e-9}
