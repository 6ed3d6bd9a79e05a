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

from bench import batch, run, served  # noqa: E402

# the test sizes of the two drivers, by the names the tests import
SMALL_M = batch.SMALL["config"]["m"]
SMALL_SERVED = served.SMALL["traffic"]


def run_small(workload: str, seed: int = 12345, seconds: float = 0.5,
              trace: bool = False, root: str = ROOT, **kw):
    """One CPU run of a cell through the harness at its driver's test size
    (``SMALL`` of the driver module).  The execution telemetry it leaves
    behind is cleared, so it cannot steer ``backend="auto"`` in tests that
    run later in the same process."""
    from repro import engine
    cell = run.find_workload(run.load_benchmark(root), workload)
    small = run.driver_module(run.load_traffic(cell["traffic"], root),
                              root).SMALL
    kw.setdefault("config_override", small["config"])
    kw.setdefault("traffic_override", small["traffic"])
    try:
        return run.run_cell(workload, seed, seconds, trace, root=root,
                            on_chip=False, **kw)
    finally:
        engine.clear_telemetry()
