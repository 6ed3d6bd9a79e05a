"""Kernel-layer benchmark: the Pallas chess_hvp v2 (interpret mode on CPU
-- numbers are for CORRECTNESS-path parity, Mosaic compiles it on real TPU)
vs the XLA L2 schedule, the v2 symmetric-vs-full and ragged-vs-divisible
comparisons, the joint-tune-vs-static-priority regret table (written to
``BENCH_pr3.json``), and the fused hdual_linear arithmetic-intensity model.

The regret table is the PR 3 acceptance artifact: every (backend, csize)
combo the joint tuner sweeps is measured once, and three selection rules
are scored against the measured best --

  joint      : argmin over the FULL joint grid (what ``csize="autotune"``
               now picks; a superset of the csize-only grid, so its regret
               is <= the PR 1 tuner's by construction *and* by measurement)
  csize_only : argmin over csize at the static-priority backend (the PR 1
               one-dimensional tuner)
  static     : §5 op-model csize at the static-priority backend (no
               measurement at all -- ``csize="auto"``)

The LIVE ``engine.autotune`` winner is recorded alongside so drift between
the bench grid and the tuner's own probes is visible -- and it carries the
real assertion: the live pick re-timed in this grid must land at-or-near
the csize-only pick (modulo timing noise between the two passes), so a
tuner regression fails the bench rather than hiding behind the grid
argmin's tautological 1.0x.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro import engine
from repro.core import testfns
from repro.kernels.ops import hdual_linear

NS = (8, 16)
FUNCS = ("rosenbrock", "ackley", "fletcher_powell")


def _data(m, n, seed=0):
    rng = np.random.RandomState(seed)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    return A, V


def _grid_backends():
    # mirror the joint tuner's candidate rule: interpret-mode pallas is a
    # correctness path, only a real TPU should spend regret budget on it
    names = ["vmap_l2", "vmap_l1", "vmap_l0"]
    if jax.default_backend() == "tpu":
        names.append("pallas")
    return names


def run_joint_tune_regret(ns, funcs, m):
    """Measure the joint grid, score the three selection rules, return the
    BENCH_pr3 records."""
    records = []
    for fname in funcs:
        for n in ns:
            f = testfns.FUNCTIONS[fname](n)
            A, V = _data(m, n, seed=n)
            grid = {}
            for bk in _grid_backends():
                for c in engine.csize_candidates(n):
                    p = engine.plan(f, n, m=m, csize=c, backend=bk,
                                    symmetric=False)
                    grid[(bk, c)] = time_fn(p.batched_hvp, A, V) / m * 1e6

            best_key = min(grid, key=grid.get)
            static_bk = "pallas" if "pallas" in _grid_backends() else "vmap_l2"
            joint_key = best_key          # argmin over the full joint grid
            csize_only_key = min(
                ((bk, c) for bk, c in grid if bk == static_bk),
                key=grid.get)
            static_key = (static_bk, engine.model_csize(n, False))

            live = engine.autotune(f, n, m=m, symmetric=False, reps=3)
            live_key = (live.backend, live.csize)
            rec = {
                "function": fname, "n": n, "m": m,
                "best": {"backend": best_key[0], "csize": best_key[1],
                         "us_per_point": round(grid[best_key], 3)},
                "live_autotune": {"backend": live.backend,
                                  "csize": live.csize, "blk_m": live.blk_m,
                                  "agrees": live_key == joint_key},
                "grid_us_per_point": {
                    bk: {str(c): round(t, 3)
                         for (b2, c), t in sorted(grid.items()) if b2 == bk}
                    for bk in _grid_backends()},
            }
            for label, key in (("joint", joint_key),
                               ("csize_only", csize_only_key),
                               ("static", static_key)):
                t = grid[key]
                rec[label] = {"backend": key[0], "csize": key[1],
                              "us_per_point": round(t, 3),
                              "regret": round(t / grid[best_key], 4)}
            # the ACCEPTANCE check is on the LIVE tuner's pick re-timed in
            # this grid (joint_key is the grid argmin, its regret is 1.0 by
            # construction and asserts nothing): the winner the tuner
            # actually returns must not be a gross regression against the
            # baselines it claims to beat.  The margin is wide (2x) because
            # the tuner's probes and this grid are two independent timing
            # passes on a noisy CPU -- picks legitimately disagree by
            # ~1.5x between passes -- while a degenerate tuner (e.g. one
            # ignoring measurements entirely) lands 2-6x out and fails
            if live_key in grid:
                live_regret = grid[live_key] / grid[best_key]
                rec["live_autotune"]["us_per_point"] = round(
                    grid[live_key], 3)
                rec["live_autotune"]["regret"] = round(live_regret, 4)
                assert live_regret <= 2.0 * max(
                    rec["csize_only"]["regret"], rec["static"]["regret"],
                    1.0), rec
            records.append(rec)
            emit(f"kernel/joint_tune/{fname}/n{n}",
                 f"{rec['joint']['backend']}/c{rec['joint']['csize']}",
                 f"regret joint={rec['joint']['regret']}x "
                 f"csize_only={rec['csize_only']['regret']}x "
                 f"static={rec['static']['regret']}x")
    return records


def run_symmetric_vs_full(quick):
    """The v2 symmetric schedule skips below-diagonal chunks: compare both
    kernel schedules (and the vmap_l2 pair for scale) on the paper's test
    functions."""
    from repro.core.api import num_chunk_evals
    m, n, csize = (16, 8, 2) if quick else (32, 12, 4)
    # the structural win is deterministic: chunk evals (= second-order
    # tangent sweeps) the symmetric schedule skips.  Wall times off-TPU go
    # through the Pallas interpreter, where grid overhead and scheduler
    # noise can swamp the saving at these shapes -- they are parity
    # numbers; Mosaic on real TPU skips the work for real.
    evals_full = num_chunk_evals(n, csize, False)
    evals_sym = num_chunk_evals(n, csize, True)
    out = []
    for fname in FUNCS:
        f = testfns.FUNCTIONS[fname](n)
        A, V = _data(m, n, seed=3)
        times = {}
        for sym in (False, True):
            p = engine.plan(f, n, m=m, csize=csize, backend="pallas",
                            symmetric=sym)
            times[f"pallas_{'sym' if sym else 'full'}"] = \
                time_fn(p.batched_hvp, A, V) / m * 1e6
            p2 = engine.plan(f, n, m=m, csize=csize, backend="vmap_l2",
                             symmetric=sym)
            times[f"vmap_l2_{'sym' if sym else 'full'}"] = \
                time_fn(p2.batched_hvp, A, V) / m * 1e6
        speedup = times["pallas_full"] / times["pallas_sym"]
        emit(f"kernel/symmetric_sweep/{fname}",
             f"{speedup:.2f}x",
             f"n={n},csize={csize}; tangent sweeps {evals_full} -> "
             f"{evals_sym}; full {times['pallas_full']:.1f} -> "
             f"sym {times['pallas_sym']:.1f} us/pt (interpret mode off-TPU)")
        out.append({"function": fname, "n": n, "m": m, "csize": csize,
                    "chunk_evals": {"full": evals_full, "sym": evals_sym},
                    "us_per_point": {k: round(v, 3)
                                     for k, v in times.items()},
                    "pallas_sym_speedup": round(speedup, 3)})
    return out


def run_pr6_symmetric_wallclock(quick):
    """PR 6 acceptance artifact: symmetric-vs-full WALL CLOCK (not just
    sweep counts) per backend across an n sweep, written to the "kernel"
    section of ``BENCH_pr6.json``.

    The compacted kernel v3 grid and the vmap_l2 cell enumeration execute
    exactly the kept triangle, so the speedup tracks the sweep ratio
    ~2*nchunk/(nchunk+1); the bench asserts the acceptance bar -- >= 1.4x
    at the largest benchmarked n on at least one backend -- so a schedule
    regression (e.g. reintroducing predicated ghost cells) fails the job
    in wall clock, not only in the roofline cell gate."""
    from benchmarks.common import update_bench_json
    from repro.core.api import num_chunk_evals
    from repro.kernels.chess_hvp import kernel_grid

    shapes = {
        "vmap_l2": [(16, 24, 4), (16, 32, 4)] if quick else
                   [(32, 24, 4), (32, 48, 4), (32, 64, 8)],
        # interpret-mode pallas: small cell, parity-path wall clock off-TPU
        "pallas": [(8, 8, 4)] if quick else [(16, 12, 4)],
    }
    blk_m = 128
    records = []
    for backend, shape_list in shapes.items():
        for m, n, csize in shape_list:
            f = testfns.FUNCTIONS["rosenbrock"](n)
            A, V = _data(m, n, seed=n)
            times, cells = {}, {}
            for sym in (False, True):
                p = engine.plan(f, n, m=m, csize=csize, backend=backend,
                                symmetric=sym, blk_m=blk_m)
                key = "sym" if sym else "full"
                times[key] = time_fn(p.batched_hvp, A, V, reps=5) / m * 1e6
                cells[key] = (kernel_grid(m, n, csize, blk_m, sym)[1]
                              if backend == "pallas" else
                              num_chunk_evals(n, csize, sym))
            speedup = times["full"] / times["sym"]
            emit(f"kernel/pr6_wallclock/{backend}/n{n}", f"{speedup:.2f}x",
                 f"csize={csize}; cells {cells['full']} -> {cells['sym']}; "
                 f"full {times['full']:.1f} -> sym {times['sym']:.1f} us/pt")
            records.append({
                "backend": backend, "m": m, "n": n, "csize": csize,
                "cells": cells,
                "us_per_point": {k: round(v, 3) for k, v in times.items()},
                "sym_speedup": round(speedup, 3)})
    # acceptance: >= 1.4x at the largest benchmarked n on >= 1 backend
    best_at_largest = {}
    for r in records:
        b = r["backend"]
        if b not in best_at_largest or r["n"] > best_at_largest[b]["n"]:
            best_at_largest[b] = r
    top = max(best_at_largest.values(), key=lambda r: r["sym_speedup"])
    assert top["sym_speedup"] >= 1.4, best_at_largest
    payload = {"records": records,
               "largest_n_speedups": {b: {"n": r["n"],
                                          "sym_speedup": r["sym_speedup"]}
                                      for b, r in best_at_largest.items()}}
    path = update_bench_json("BENCH_pr6.json", "kernel", payload,
                             env_var="BENCH_PR6_OUT")
    emit("kernel/pr6_bench_json", path,
         f"best largest-n speedup {top['sym_speedup']}x ({top['backend']})")
    return records


def run_ragged_vs_divisible(quick):
    """Before v2 the kernel only ran csize | n; at n=12 that capped chunks
    at csize=4.  Measure what the ragged tail unlocks: csize=8 (one ragged
    chunk of 4 masked lanes) vs the old best divisor, same f, same data."""
    m, n = (16, 12) if quick else (32, 12)
    out = []
    for fname in FUNCS:
        f = testfns.FUNCTIONS[fname](n)
        A, V = _data(m, n, seed=7)
        times = {}
        for label, csize in (("divisible_c4", 4), ("ragged_c8", 8),
                             ("ragged_c16", 16)):
            p = engine.plan(f, n, m=m, csize=csize, backend="pallas",
                            symmetric=False)
            times[label] = time_fn(p.batched_hvp, A, V) / m * 1e6
        emit(f"kernel/ragged_tail/{fname}",
             f"c8 {times['ragged_c8']:.1f} us/pt",
             f"n={n}; old divisor cap c4 {times['divisible_c4']:.1f}; "
             f"single over-wide chunk c16 {times['ragged_c16']:.1f}")
        out.append({"function": fname, "n": n, "m": m,
                    "us_per_point": {k: round(v, 3)
                                     for k, v in times.items()}})
    return out


def run(quick=False):
    m, n, csize = (32, 8, 2) if quick else (64, 16, 4)
    A, V = _data(m, n)

    f = testfns.rosenbrock
    p_xla = engine.plan(f, n, m=m, csize=csize, backend="vmap_l2",
                        symmetric=False)
    t_xla = time_fn(p_xla.batched_hvp, A, V)
    emit("kernel/chess_hvp/xla_L2_us_per_point", f"{t_xla / m * 1e6:.2f}",
         f"m={m},n={n}")
    p_pl = engine.plan(f, n, m=m, csize=csize, backend="pallas",
                       symmetric=False, blk_m=128)
    t_pl = time_fn(p_pl.batched_hvp, A, V)
    emit("kernel/chess_hvp/pallas_interpret_us_per_point",
         f"{t_pl / m * 1e6:.2f}", "interpret=True (CPU correctness path)")

    # -- PR 6: symmetric-vs-full wall clock, written to BENCH_pr6.json -----
    run_pr6_symmetric_wallclock(quick)

    # -- PR 3: symmetric schedule, ragged tails, joint-tune regret ---------
    sym_records = run_symmetric_vs_full(quick)
    ragged_records = run_ragged_vs_divisible(quick)
    regret_records = run_joint_tune_regret(
        ns=(8,) if quick else NS,
        funcs=FUNCS[:2] if quick else FUNCS,
        m=16 if quick else 64)

    out = {
        "bench": "kernel_joint_tune",
        "platform": jax.default_backend(),
        "pallas_interpret": jax.default_backend() != "tpu",
        "joint_tune_regret": regret_records,
        "symmetric_vs_full": sym_records,
        "ragged_vs_divisible": ragged_records,
    }
    path = os.environ.get("BENCH_PR3_OUT", "BENCH_pr3.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)
        fh.write("\n")
    emit("kernel/bench_json", path,
         f"{len(regret_records)} regret records")

    # hdual_linear: HBM-traffic model for the fused kernel
    rng = np.random.RandomState(0)
    K2, T, d = (2 * csize + 2), 256, 256
    x = jnp.asarray(rng.randn(K2, T, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, d), jnp.float32)
    t_fused = time_fn(lambda: hdual_linear(x, w, bt=64, bo=64, bk=64))
    emit("kernel/hdual_linear/pallas_interpret_ms", f"{t_fused * 1e3:.1f}",
         f"K2={K2},T={T},d={d}")
    naive_w_bytes = K2 * d * d * 4           # W re-read per component
    fused_w_bytes = d * d * 4                # W tiles read once
    emit("kernel/hdual_linear/w_traffic_reduction",
         f"{naive_w_bytes / fused_w_bytes:.0f}x",
         "arithmetic-intensity win = 2c+2 (DESIGN.md §3)")


def main(quick: bool = False):
    run(quick)


if __name__ == "__main__":
    main()
