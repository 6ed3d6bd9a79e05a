"""The harness drives a whole run (all but its look for a chip) with the
timed path broken underneath, and ``correct`` comes out false: once for
each fault the cells can have -- an answer altered where it is produced,
half of a batch left out, and an answer that never comes."""

import numpy as np
import pytest

from bench_helpers import run_small
from bench import served


def _alter_first_row(out):
    out = np.array(out)
    out[0, 0] *= 1.01
    return out


def test_batch_answer_altered(monkeypatch):
    from repro import engine
    real_plan = engine.plan

    class Altered:
        def __init__(self, p):
            self.p, self.csize = p, p.csize

        def batched_hvp(self, A, V):
            import jax.numpy as jnp
            return jnp.asarray(_alter_first_row(self.p.batched_hvp(A, V)))

        def backend_for(self, workload):
            return self.p.backend_for(workload)

    monkeypatch.setattr(engine, "plan",
                        lambda *a, **kw: Altered(real_plan(*a, **kw)))
    res = run_small("rosenbrock.batch-paper", seed=3)
    assert res["correct"] is False
    assert res["compared"]["max_row_rel_err"]["value"] > 1e-3


def test_batch_half_left_out(monkeypatch):
    """The kernel answers only the first half of the rows; the rest of the
    output is left at zero."""
    from repro import engine
    real_plan = engine.plan

    class Half:
        def __init__(self, p):
            self.p, self.csize = p, p.csize

        def batched_hvp(self, A, V):
            import jax.numpy as jnp
            half = A.shape[0] // 2
            out = self.p.batched_hvp(A[:half], V[:half])
            return jnp.concatenate([out, jnp.zeros_like(out)])

        def backend_for(self, workload):
            return self.p.backend_for(workload)

    monkeypatch.setattr(engine, "plan",
                        lambda *a, **kw: Half(real_plan(*a, **kw)))
    res = run_small("fletcher_powell.batch-paper", seed=6)
    assert res["correct"] is False
    assert res["compared"]["max_row_rel_err"]["value"] >= 1.0


# the served driver's two arrival processes: the cell's closed loop, and
# the open loop (Poisson arrivals) that the traffic file can ask for
ARRIVALS = {"closed": {}, "poisson": {"arrivals": "poisson"}}


@pytest.mark.parametrize("arrivals", sorted(ARRIVALS))
def test_served_answer_altered(arrivals, monkeypatch):
    from repro.engine.plan import CurvaturePlan
    real = CurvaturePlan.executable

    def executable(self, workload):
        fn = real(self, workload)
        return lambda *args: _alter_first_row(fn(*args))

    monkeypatch.setattr(CurvaturePlan, "executable", executable)
    res = run_small("rosenbrock.served-closed", seed=4,
                    traffic_override={**_small(), **ARRIVALS[arrivals]})
    assert res["correct"] is False
    assert res["compared"]["max_request_rel_err"]["value"] > 1e-3


def test_served_answer_never_comes(monkeypatch):
    from repro.serving.scheduler import Scheduler
    real = Scheduler.take_ready_batch
    dropped = []

    def take_ready_batch(self, now, force=False):
        got = real(self, now, force)
        if got is not None and not dropped and len(got[1]) > 1:
            q, reqs = got
            dropped.append(reqs[0])
            got = (q, reqs[1:])
        return got

    monkeypatch.setattr(Scheduler, "take_ready_batch", take_ready_batch)
    monkeypatch.setattr(served, "DRAIN_S", 1.0)
    res = run_small("rosenbrock.served-closed", seed=5,
                    traffic_override={**_small(), **ARRIVALS["poisson"],
                                      "warm_seconds": 0.0})
    assert dropped
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert res["compared"]["failed"]["value"] >= 1


def _small():
    from bench_helpers import SMALL_SERVED
    return dict(SMALL_SERVED)
