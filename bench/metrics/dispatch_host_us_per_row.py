"""The dispatch worker's host time per dispatched row in the traced window:
its ``repro:coalesce``, ``marshal``, ``readback`` and ``respond`` spans,
summed and divided by the rows dispatched (dispatch layer; moves
served_rps: in a closed loop the worker's time per bucket paces every
caller).  ``reply`` runs inside ``respond`` and is not counted twice.

Rows: the ``repro:device_execute`` spans in the window times the mean rows
per bucket of the run's counters (the trace keeps span names, not their
stats).  Fields: microseconds per row of each stage and of
``device_execute``; the worker's busy and waiting shares of the window
(summed over workers); the buckets; and ``idle_s_by_stage``, the device's
idle seconds split among the worker's stages by interval intersection."""

from bench import host_spans as hs


def read(run):
    batches = run.counters.get("batches", 0)
    if run.timeline is None or not batches:
        return None
    lo, hi = run.window_ns
    by_stage = {s: hs.spans(run.timeline, s, lo, hi) for s in hs.WORKER}
    buckets = len(by_stage["device_execute"])
    if not buckets:
        return None
    rows = buckets * run.counters["dispatched"] / batches
    per_row = {s: hs.total_ns(by_stage[s]) * 1e-3 / rows
               for s in hs.WORKER_HOST + ("device_execute",)}
    busy = sum(hs.total_ns(by_stage[s])
               for s in hs.WORKER_HOST + ("device_execute",))
    return {"value": sum(per_row[s] for s in hs.WORKER_HOST),
            **{f"{s}_us": us for s, us in per_row.items()},
            "worker_busy_pct": 100.0 * busy / (hi - lo),
            "worker_wait_pct": 100.0 * hs.total_ns(by_stage["worker_wait"])
            / (hi - lo),
            "buckets": buckets, "rows": rows,
            "idle_s_by_stage": hs.idle_by_stage(run.timeline, hs.WORKER,
                                                lo, hi)}
