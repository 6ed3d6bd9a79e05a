"""95th percentile, over every request of the window, of the time it
waited in admission and the scheduler: the program's own ``enqueue`` +
``coalesce`` + ``dispatch_wait`` spans (moves served_rps: in a closed
loop, less waiting per request is more requests per second)."""

import numpy as np


def read(run):
    waits = run.counters.get("queue_wait_s") or []
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits), 95)) * 1e3
