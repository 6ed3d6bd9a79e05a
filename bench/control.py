"""The control of a cell's comparison, run on the chip at the cell's size.

    python3 -m bench.control --workload <cell> --seeds 1,2,3

The control is the reference put in the program's place, computed one
precision below the configuration's float32: bfloat16, on the device.  For
each seed it makes the cell's inputs as its driver does (a batch cell's
input sets, a served cell's request pool), answers them with the control,
and prints the number the cell compares -- the widest per-row gap to the
float64 reference -- beside the cell's limit.  The benchmark's own runs
never run it; ``tests/bench/test_bench_control.py`` keeps it at a size a
test can hold.
"""

from __future__ import annotations

import argparse
import json
import sys

from . import run


def control_reading(workload: str, seed: int, dtype: str = "bfloat16",
                    config_override: dict | None = None,
                    traffic_override: dict | None = None) -> dict:
    import numpy as np
    from . import batch, reference, served
    from .common import device_key, host_rng
    bench = run.load_benchmark()
    cell = run.find_workload(bench, workload)
    config = run.load_config(bench, cell["config"])
    config.spec.update(config_override or {})
    traffic = {**run.load_traffic(cell["traffic"]),
               **(traffic_override or {})}
    groups = []             # (n, A, V) answered by the control
    if traffic["driver"] == "batch":
        n, m = config.spec["n"], config.spec["m"]
        A, V = batch._inputs(device_key(seed), int(traffic["input_sets"]),
                             m, n, float(traffic["a_low"]),
                             float(traffic["a_high"]))
        groups = [(n, np.asarray(A[s]), np.asarray(V[s]))
                  for s in range(A.shape[0])]
    else:
        widths, pool = served._pool(
            host_rng(seed, 1), traffic["n_mix"], int(traffic["pool"]),
            float(traffic["a_low"]), float(traffic["a_high"]))
        for n in sorted(set(int(w) for w in widths)):
            rows = [p for p, w in zip(pool, widths) if w == n]
            groups.append((n, np.stack([a for a, _ in rows]),
                           np.stack([v for _, v in rows])))
    worst = 0.0
    for n, A, V in groups:
        f = config.module.formula(n)
        low = reference.hvp_in(f, A, V, dtype)
        ref = reference.hvp_float64(f, A, V)
        worst = max(worst, float(reference.row_rel_err(low, ref).max()))
    return {"workload": workload, "seed": seed, "dtype": dtype,
            "value": worst, "limit": float(config.spec["max_rel_err"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    run._prepare_environment(run.ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("bench.control: no TPU", file=sys.stderr)
        return run.NO_CHIP_EXIT
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_reading(args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
