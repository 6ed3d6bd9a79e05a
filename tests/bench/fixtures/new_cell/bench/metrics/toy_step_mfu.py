"""The whole call's share of the chip's peak FLOP/s (moves points_per_s)."""

from bench.device_metrics import peak_share_pct


def read(run):
    return peak_share_pct(run, "bench:toy_hvp", run.counters["flops_per_call"])
