"""Share of the traced window in which no operation ran on the device, in
the closed-loop served cell (moves served_rps)."""

from bench.device_metrics import idle_pct


def read(run):
    return idle_pct(run)
