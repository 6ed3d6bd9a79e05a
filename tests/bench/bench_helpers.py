"""Shared set-up of the benchmark's CPU tests: the repo root on the path,
and runs of the harness at sizes a test can hold."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run  # noqa: E402

SMALL_M = 256
SMALL_SERVED = {"n_mix": {"8": 0.5, "12": 0.5}, "pool": 128,
                "warm_seconds": 0.2, "callers": 8, "rate_rps": 200.0,
                "server": {"max_batch": 4, "max_wait_us": 500.0}}


def run_small(workload: str, seed: int = 12345, seconds: float = 0.5,
              trace: bool = False, root: str = ROOT, **kw):
    """One CPU run of a cell through the harness at a test size.  The
    execution telemetry it leaves behind is cleared, so it cannot steer
    ``backend="auto"`` in tests that run later in the same process."""
    from repro import engine
    if ".batch" in workload:
        kw.setdefault("config_override", {"m": SMALL_M})
    else:
        kw.setdefault("traffic_override", SMALL_SERVED)
    try:
        return run.run_cell(workload, seed, seconds, trace, root=root,
                            on_chip=False, **kw)
    finally:
        engine.clear_telemetry()
