"""The control of the comparison that decides ``correct``: the reference
itself, computed one precision lower (bfloat16 for the configurations'
float32) and put in the program's place, has to come out as not correct,
while the program passes.  At a size a test run can hold; on the chip the
same comparison runs at the cells' own sizes."""

import jax
import numpy as np
import pytest

from bench_helpers import SMALL_SERVED, run, run_small
from bench import reference, served
from bench.common import host_rng


class _ControlPlan:
    """The bfloat16 reference where the plan would be."""

    def __init__(self, formula, n):
        self.formula, self.n, self.csize = formula, n, None

    def batched_hvp(self, A, V):
        return jax.numpy.asarray(reference.hvp_in(
            self.formula, np.asarray(A), np.asarray(V), "bfloat16"))

    def backend_for(self, workload):
        return "bfloat16-reference"


@pytest.mark.parametrize("config", ["rosenbrock", "fletcher_powell"])
def test_program_passes(config):
    res = run_small(f"{config}.batch-paper", seed=99)
    assert res["correct"] is True
    (value, limit), = [(c["value"], c["limit"]) for k, c in
                       res["compared"].items() if k != "failed"]
    assert value < limit / 10


@pytest.mark.parametrize("config", ["rosenbrock", "fletcher_powell"])
def test_control_in_the_programs_place_fails(config, monkeypatch):
    from repro import engine
    cfg = run.load_config(run.load_benchmark(), config)
    monkeypatch.setattr(engine, "plan", lambda f, n, **kw: _ControlPlan(
        cfg.module.formula(n), n))
    res = run_small(f"{config}.batch-paper", seed=99)
    assert res["correct"] is False
    c = res["compared"]["max_row_rel_err"]
    assert c["value"] > 3 * c["limit"]


def test_control_fails_on_the_served_requests():
    """The served cells' requests, answered by the bfloat16 reference."""
    cfg = run.load_config(run.load_benchmark(), "rosenbrock")
    traffic = {**run.load_traffic("served-closed"), **SMALL_SERVED}
    widths, pool = served._pool(host_rng(7, 1), traffic["n_mix"],
                                traffic["pool"], traffic["a_low"],
                                traffic["a_high"])
    worst = 0.0
    for n in sorted(set(widths)):
        A = np.stack([a for (a, _), w in zip(pool, widths) if w == n])
        V = np.stack([v for (_, v), w in zip(pool, widths) if w == n])
        f = cfg.module.formula(int(n))
        ref = reference.hvp_float64(f, A, V)
        low = reference.hvp_in(f, A, V, "bfloat16", jax.devices("cpu")[0])
        worst = max(worst, reference.row_rel_err(low, ref).max())
    assert worst > 3 * cfg.spec["max_rel_err"]
