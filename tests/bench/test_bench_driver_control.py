"""Each driver module gives the control reading of its cells
(``control_reading``), and ``bench.control`` dispatches to it.  At a test
size, on the CPU, the reading is exactly the widest per-row gap of the
bfloat16 reference to the float64 reference on the driver's own inputs,
worked out here from ``bench.reference`` directly."""

import jax
import numpy as np
import pytest

from bench_helpers import SMALL_M, SMALL_SERVED, run
from bench import batch, control, reference, served
from bench.common import device_key, host_rng

SEEDS = [7, 2 ** 33 + 5]


def _gap(formula, A, V):
    low = reference.hvp_in(formula, A, V, "bfloat16", jax.devices("cpu")[0])
    return float(reference.row_rel_err(
        low, reference.hvp_float64(formula, A, V)).max())


def _setup(workload, config_override, traffic_override):
    bench = run.load_benchmark()
    cell = run.find_workload(bench, workload)
    config = run.load_config(bench, cell["config"])
    config.spec.update(config_override)
    return config, {**run.load_traffic(cell["traffic"]), **traffic_override}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config", ["rosenbrock", "fletcher_powell"])
def test_batch_control_reading_is_the_direct_gap(config, seed):
    cfg, traffic = _setup(f"{config}.batch-paper", {"m": SMALL_M}, {})
    n, m = cfg.spec["n"], cfg.spec["m"]
    A, V = batch._inputs(device_key(seed), traffic["input_sets"], m, n,
                         traffic["a_low"], traffic["a_high"])
    want = max(_gap(cfg.module.formula(n), np.asarray(A[s]),
                    np.asarray(V[s])) for s in range(A.shape[0]))
    assert batch.control_reading(cfg, traffic, seed, "bfloat16") == want
    got = control.control_reading(f"{config}.batch-paper", seed,
                                  config_override={"m": SMALL_M})
    assert got["value"] == want
    assert got["value"] > 3 * got["limit"]


@pytest.mark.parametrize("seed", SEEDS)
def test_served_control_reading_is_the_direct_gap(seed):
    cfg, traffic = _setup("rosenbrock.served-closed", {}, SMALL_SERVED)
    widths, pool = served._pool(host_rng(seed, 1), traffic["n_mix"],
                                traffic["pool"], traffic["a_low"],
                                traffic["a_high"])
    want = 0.0
    for n in sorted(set(int(w) for w in widths)):
        A = np.stack([a for (a, _), w in zip(pool, widths) if w == n])
        V = np.stack([v for (_, v), w in zip(pool, widths) if w == n])
        want = max(want, _gap(cfg.module.formula(n), A, V))
    assert served.control_reading(cfg, traffic, seed, "bfloat16") == want
    got = control.control_reading("rosenbrock.served-closed", seed,
                                  traffic_override=SMALL_SERVED)
    assert got["value"] == want
    assert got["value"] > 3 * got["limit"]


def test_each_driver_gives_its_test_size_and_control():
    for w in run.load_benchmark()["workloads"]:
        mod = run.driver_module(run.load_traffic(w["traffic"]))
        assert set(mod.SMALL) == {"config", "traffic"}
        assert callable(mod.control_reading)
