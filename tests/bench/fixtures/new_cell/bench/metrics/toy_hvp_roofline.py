"""The pytree HVP's share of its roofline (moves points_per_s): the work
the driver counts per call, over the device time inside the calls."""

from bench.device_metrics import share_of_roofline
from bench.work import Work


def read(run):
    return share_of_roofline(run, "bench:toy_hvp",
                             Work(flops=run.counters["flops_per_call"],
                                  bytes=run.counters["bytes_per_call"]))
