"""Host time by serving stage, from the program's ``repro:`` annotations.

While a profiler capture runs, the serving stack wraps each host stage of
a request in a ``repro:<stage>`` annotation (docs/observability.md): the
connection reader's ``decode`` and ``submit``, the dispatch worker's
``coalesce``, ``marshal``, ``device_execute``, ``readback``, ``respond``
(with the frontend's ``reply`` nested in it) and ``worker_wait``, and the
client's ``client_send`` and ``client_recv``.  ``bench/trace.load`` keeps
their names and extents on the device trace's clock; the functions below
sum them inside the traced window and split the device's idle time among
them by interval intersection.  Every reader built on them returns None
where the program carries no such annotation.
"""

from __future__ import annotations

from . import trace as tr

__all__ = ["PREFIX", "WORKER_HOST", "WORKER", "spans", "total_ns",
           "overlap_ns", "idle_by_stage"]

PREFIX = "repro:"
# the dispatch worker's host stages, one after another per bucket; reply
# runs inside respond, so it is not one of them
WORKER_HOST = ("coalesce", "marshal", "readback", "respond")
# every stage the worker thread passes through
WORKER = WORKER_HOST + ("device_execute", "worker_wait")


def spans(timeline, stage: str, lo, hi) -> list:
    """The ``repro:<stage>`` spans that overlap [lo, hi], cut to it."""
    return tr.clip(timeline.spans_named(PREFIX + stage), lo, hi)


def total_ns(intervals) -> float:
    return float(sum(t1 - t0 for t0, t1 in intervals))


def overlap_ns(a, b) -> float:
    """Time covered by both interval sets (each merged first)."""
    a, b = tr.merge(a), tr.merge(b)
    out, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return float(out)


def idle_by_stage(timeline, stages, lo, hi):
    """{stage: seconds}: the device's idle time in [lo, hi] that falls
    inside each stage's spans, and ``"no worker span"`` for the idle time
    inside none of them.  Gaps are cut by interval intersection, not named
    by their midpoints: one idle gap per bucket spans several stages.
    None where the trace holds no device."""
    if not timeline.ops:
        return None
    idle = tr.gaps(timeline.all_ops(), lo, hi)
    by_stage = {s: spans(timeline, s, lo, hi) for s in stages}
    out = {s: overlap_ns(idle, iv) * 1e-9 for s, iv in by_stage.items()}
    covered = overlap_ns(idle, [iv for ivs in by_stage.values()
                                for iv in ivs])
    out["no worker span"] = (total_ns(idle) - covered) * 1e-9
    return out
