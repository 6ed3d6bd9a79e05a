"""Distributed (L1 row-sharded) HVP benchmark: rows/sec vs mesh shape.

The paper's claim behind the ``sharded_rows`` backend is that Hessian rows
are independent, so a single large-n HVP scales with the number of row
shards.  This suite measures the engine-planned sharded_rows executable
across model-axis sizes, plus the single-device vmap_l2 baseline, and
writes ``BENCH_pr4.json``.

It runs in the calling process, on the mesh that ``jax.devices()`` gives:
a chip belongs to one process, so the suite never starts a child.  Model
axis sizes are the divisors of the device count (up to 8).  For a CPU
rehearsal, fake host devices come from the environment set outside, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; those all share
one CPU, so their rows/sec record the schedule, not scaling.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro import engine
from repro.compat import make_mesh
from repro.core import testfns

MODEL_SIZES = (1, 2, 4, 8)
NS = (64, 96)          # 96 = ragged on every model size but 1 with csize 8
QUICK_NS = (32,)


def _median_hvp_s(p, a, v, reps: int) -> float:
    jax.block_until_ready(p.hvp(a, v))              # compile + warmup
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(p.hvp(a, v))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _mesh(size: int):
    return make_mesh((len(jax.devices()) // size, size), ("data", "model"))


def _device_label() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind} x{len(jax.devices())}"


def _rows_records(ns, model_sizes, csize: int) -> list:
    records = []
    rng = np.random.RandomState(0)
    for n in ns:
        f = testfns.FUNCTIONS["rosenbrock"](n)
        a = jnp.asarray(rng.uniform(-2, 2, (n,)), jnp.float32)
        v = jnp.asarray(rng.randn(n), jnp.float32)
        for size in model_sizes:
            for sym in (False, True):
                if size == 1:
                    p = engine.plan(f, n, csize=csize, symmetric=sym)
                else:
                    p = engine.plan(f, n, csize=csize, mesh=_mesh(size),
                                    symmetric=sym)
                backend = p.backend_for("hvp")
                assert size == 1 or backend == "sharded_rows", backend
                t = _median_hvp_s(p, a, v, reps=3)
                records.append({
                    "n": n, "csize": csize, "model_axis_size": size,
                    "symmetric": sym, "backend": backend,
                    "mesh_shape": ("1 device" if size == 1 else
                                   f"{len(jax.devices()) // size}x{size}"),
                    "hvp_s": round(t, 6),
                    "rows_per_sec": round(n / t, 1),
                })
    return records


# PR 6: symmetric wall clock on the row-sharded backend -- the compacted
# cyclic layout vs the masked block layout vs the full schedule.  Fake
# devices serialize on one CPU, which makes them an honest TOTAL-WORK clock:
# the masked block layout executes the full grid's cells even when half are
# predicated away, so skipping shows up directly.
def _pr6_records(ns, csize: int, size: int) -> list:
    from repro.core.api import num_chunk_evals
    from repro.core.distributed import cyclic_layout, rows_per_shard
    mesh = _mesh(size)
    records = []
    rng = np.random.RandomState(0)
    for n in ns:
        f = testfns.FUNCTIONS["rosenbrock"](n)
        a = jnp.asarray(rng.uniform(-2, 2, (n,)), jnp.float32)
        v = jnp.asarray(rng.randn(n), jnp.float32)
        variants = {
            "full": dict(symmetric=False),
            "sym_block": dict(symmetric=True, row_layout="block"),
            "sym_cyclic": dict(symmetric=True, row_layout="cyclic"),
        }
        times = {}
        for label, kw in variants.items():
            p = engine.plan(f, n, csize=csize, mesh=mesh, **kw)
            assert p.backend_for("hvp") == "sharded_rows"
            times[label] = _median_hvp_s(p, a, v, reps=5)
        lay = cyclic_layout(n, csize, size)
        grid_cells = size * rows_per_shard(n, size) * (-(-n // csize))
        records.append({
            "n": n, "csize": csize, "model_axis_size": size,
            "hvp_s": {k: round(t, 6) for k, t in times.items()},
            "cells": {"full": num_chunk_evals(n, csize, False),
                      "sym_block_executed": grid_cells,
                      "sym_cyclic_executed": size * lay.executed,
                      "sym_kept": num_chunk_evals(n, csize, True)},
            "sym_cyclic_speedup_vs_full":
                round(times["full"] / times["sym_cyclic"], 3),
            "cyclic_speedup_vs_block":
                round(times["sym_block"] / times["sym_cyclic"], 3),
        })
    return records


def _model_sizes(wanted) -> tuple:
    """The requested model-axis sizes that divide the device count."""
    devices = len(jax.devices())
    return tuple(s for s in wanted if s <= devices and devices % s == 0)


def run_pr6(quick: bool = False, size: int = 4):
    """Symmetric wall-clock sweep for sharded_rows, merged into the
    "distributed" section of BENCH_pr6.json.  Skipped where the devices
    do not split into a ``size``-wide model axis."""
    from benchmarks.common import update_bench_json
    if not _model_sizes((size,)):
        emit("distributed/pr6_wallclock", "skipped",
             f"{_device_label()}: no {size}-wide model axis")
        return []
    ns = (32,) if quick else (48, 64)
    records = _pr6_records(ns, csize=4, size=size)
    for rec in records:
        emit(f"distributed/pr6_wallclock/n{rec['n']}",
             f"{rec['sym_cyclic_speedup_vs_full']}x vs full",
             f"cyclic-vs-block {rec['cyclic_speedup_vs_block']}x; cells "
             f"{rec['cells']['full']} -> {rec['cells']['sym_cyclic_executed']}"
             f" executed / {rec['cells']['sym_kept']} kept "
             f"({_device_label()})")
    payload = {
        "device": _device_label(),
        "note": ("on fake host devices, which serialize on one CPU, wall "
                 "clock tracks TOTAL executed cells: the masked block "
                 "layout pays for the dropped triangle, the cyclic layout "
                 "skips it"),
        "model_axis_size": size,
        "records": records,
    }
    path = update_bench_json("BENCH_pr6.json", "distributed", payload,
                             env_var="BENCH_PR6_OUT")
    emit("distributed/pr6_bench_json", path, f"{len(records)} records")
    return records


def run(ns=NS, model_sizes=MODEL_SIZES, csize=8, out_path=None):
    records = _rows_records(ns, _model_sizes(model_sizes), csize)

    for rec in records:
        emit(f"distributed/rosenbrock/n{rec['n']}"
             f"/model{rec['model_axis_size']}"
             f"/{'sym' if rec['symmetric'] else 'full'}/rows_per_sec",
             rec["rows_per_sec"],
             f"backend={rec['backend']}, {rec['hvp_s'] * 1e3:.2f} ms "
             f"({_device_label()})")

    payload = {
        "bench": "distributed_rows",
        "device": _device_label(),
        "note": ("on fake host devices, which share one CPU, rows/sec "
                 "documents the schedule across mesh shapes, not scaling"),
        "records": records,
    }
    path = out_path or os.environ.get("BENCH_PR4_OUT", "BENCH_pr4.json")
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    emit("distributed/bench_json", path, f"{len(records)} records")


def main(quick: bool = False):
    if quick:
        run(ns=QUICK_NS, model_sizes=(1, 2, 4), csize=4)
    else:
        run()
    run_pr6(quick=quick)


if __name__ == "__main__":
    main()
