"""The batched HVP's share of its roofline (moves points_per_s).

Work: the operations of the benchmark's own reference HVP and the least
bytes (A, V read, the result written), at the configuration's (m, n), so
the same work is counted whatever implements it.  Time: the device time of
every operation inside the traced ``batched_hvp`` calls, not only the
kernel's.  The line records which bound (compute or memory) applies."""

from bench.device_metrics import roofline_pct


def read(run):
    return roofline_pct(run, "bench:batched_hvp", int(run.config.spec["n"]),
                        int(run.config.spec["m"]))
