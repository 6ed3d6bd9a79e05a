"""Requests answered per bucket dispatched in the window, from the
service's own counters (dispatch layer; moves served_rps)."""


def read(run):
    batches = run.counters.get("batches", 0)
    if not batches:
        return None
    return run.counters["dispatched"] / batches
