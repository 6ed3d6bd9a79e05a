"""The control of a cell's comparison, run on the chip at the cell's size.

    python3 -m bench.control --workload <cell> --seeds 1,2,3

The control is the reference put in the program's place, computed one
precision below the configuration's float32: bfloat16, on the device.  For
each seed the cell's driver module (``control_reading`` in
``bench/<driver>.py``) makes the cell's inputs as its ``Driver`` does,
answers them with the control, and gives the number the cell compares --
the widest per-row gap to the float64 reference -- which is printed beside
the cell's limit.  The benchmark's own runs never run it;
``tests/bench/test_bench_control.py`` keeps it at a size a test can hold.
"""

from __future__ import annotations

import argparse
import json
import sys

from . import run


def control_reading(workload: str, seed: int, dtype: str = "bfloat16",
                    config_override: dict | None = None,
                    traffic_override: dict | None = None,
                    root: str = run.ROOT) -> dict:
    bench = run.load_benchmark(root)
    cell = run.find_workload(bench, workload)
    config = run.load_config(bench, cell["config"], root)
    config.spec.update(config_override or {})
    traffic = {**run.load_traffic(cell["traffic"], root),
               **(traffic_override or {})}
    value = run.driver_module(traffic, root).control_reading(
        config, traffic, seed, dtype)
    return {"workload": workload, "seed": seed, "dtype": dtype,
            "value": value, "limit": float(config.spec["max_rel_err"])}


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
