"""Share of the traced window in which no operation ran on the device, in
the batch cells (moves points_per_s)."""

from bench.device_metrics import idle_pct


def read(run):
    return idle_pct(run)
