"""Roofline report: LLM dry-run cells AND the curvature backends.

Default mode reads artifacts/dryrun/*.json and renders the per-cell
three-term table (``hlo_analysis.roofline_terms`` with the v5e peaks the
dry run's production pod targets):

  compute    = HLO_FLOPs_per_device / 197 TFLOP/s
  memory     = HLO_bytes_per_device / 819 GB/s
  collective = wire_bytes_per_device / 50 GB/s (ICI link)

Also reports MODEL_FLOPS/HLO_FLOPs (useful-compute ratio; catches remat and
redundancy waste) and the dominant term per cell.

``--curvature`` (PR 6) instead measures the engine's curvature backends
directly: for each (backend, schedule) it compiles the batched-HVP
executable, reads HLO FLOPs/bytes from ``compiled.cost_analysis()``, times
the executable, and reports

  pct_roofline   = 100 * roofline_lower_bound / measured, with the peaks
                   of the TPU it ran on (``hlo_analysis.peaks_for``); off
                   the chip it is "not measured"
  cells_executed = the schedule's static tangent-sweep count (the pallas
                   launch grid / vmap cell enumeration / cyclic sharded
                   cell lists)
  cells_min      = the minimum sweeps the schedule is ALLOWED: the full
                   n*ceil(n/csize) grid, or the kept upper triangle for
                   symmetric (``num_chunk_evals``)

and the symmetric-vs-full wall-clock speedup per backend.  The process
exits nonzero if any symmetric schedule EXECUTES more chunk cells than the
triangle bound (single-device backends must hit it exactly; the cyclic
sharded layout gets the documented one-block-per-shard padding slack) --
the CI gate that symmetric skipping never regresses to masking.

Usage: python -m repro.launch.roofline [--dir artifacts/dryrun] [--md]
       python -m repro.launch.roofline --curvature [--quick] [--md]
           [--out table.md] [--json records.json]
"""

from __future__ import annotations

import argparse
import glob
import json
import os

__all__ = ["load_records", "render_table", "run_curvature",
           "curvature_records"]

DEFAULT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "artifacts", "dryrun")


def load_records(d: str = DEFAULT_DIR) -> list[dict]:
    recs = []
    for p in sorted(glob.glob(os.path.join(d, "*.json"))):
        with open(p) as f:
            recs.append(json.load(f))
    return recs


def _fmt_t(x):
    if x >= 1.0:
        return f"{x:7.2f}s "
    if x >= 1e-3:
        return f"{x * 1e3:7.2f}ms"
    return f"{x * 1e6:7.2f}us"


def render_table(recs: list[dict], md: bool = False) -> str:
    rows = []
    hdr = ["cell", "status", "t_compute", "t_memory", "t_collective",
           "bound", "useful_ratio", "hbm_GiB"]
    for r in recs:
        if r["status"] == "ok":
            t = r["roofline"]
            mem = r.get("memory", {})
            hbm = (mem.get("argument_size_in_bytes", 0)
                   + mem.get("temp_size_in_bytes", 0)
                   + mem.get("output_size_in_bytes", 0)
                   - mem.get("alias_size_in_bytes", 0)) / 2 ** 30
            ur = r.get("useful_flop_ratio")
            rows.append([r["cell"], "ok", _fmt_t(t["compute_s"]),
                         _fmt_t(t["memory_s"]), _fmt_t(t["collective_s"]),
                         t["bound"],
                         f"{ur:.2f}" if ur is not None else "-",
                         f"{hbm:.2f}"])
        elif r["status"] == "skipped":
            rows.append([r["cell"], "SKIP", "-", "-", "-", "-", "-", "-"])
        else:
            rows.append([r["cell"], "ERROR", "-", "-", "-", "-", "-", "-"])
    widths = [max(len(str(row[i])) for row in rows + [hdr])
              for i in range(len(hdr))]

    def line(row):
        cells = [str(c).ljust(w) for c, w in zip(row, widths)]
        return ("| " + " | ".join(cells) + " |") if md else "  ".join(cells)

    out = [line(hdr)]
    if md:
        out.append("|" + "|".join("-" * (w + 2) for w in widths) + "|")
    out += [line(r) for r in rows]
    return "\n".join(out)


# ---------------------------------------------------------------------------
# --curvature: per-backend % of roofline + achieved-sweeps vs minimum (PR 6)
# ---------------------------------------------------------------------------

def _median_time(fn, reps: int = 5) -> float:
    import statistics
    import time

    import jax
    jax.block_until_ready(fn())            # warm: compile outside the clock
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def _hlo_cost(fn, *args) -> tuple[float, float]:
    """(flops, bytes accessed) from the compiled executable's cost model."""
    import jax
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(c, (list, tuple)):       # older jax returns [dict]
        c = c[0] if c else {}
    c = c or {}
    return (float(c.get("flops") or 0.0),
            float(c.get("bytes accessed") or 0.0))


def _executed_cells(backend: str, m: int, n: int, csize: int, blk_m: int,
                    symmetric: bool) -> int:
    """The schedule's static tangent-sweep trip count -- for pallas this is
    literally the launch grid's trailing extent (kernel v3 has no
    predicated ghost cells to subtract)."""
    if backend == "pallas":
        from repro.kernels.chess_hvp import kernel_grid
        return kernel_grid(m, n, csize, blk_m, symmetric)[1]
    from repro.core.api import num_chunk_evals
    return num_chunk_evals(n, csize, symmetric)


def curvature_records(quick: bool = False) -> list[dict]:
    """Measure every curvature backend on both schedules; one record per
    (backend, schedule) plus a static accounting row for the cyclic
    sharded_rows layout (its wall clock needs a multi-device mesh; its
    sweep accounting is host-side and gated here regardless)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import engine
    from repro.core import testfns
    from repro.core.api import num_chunk_evals
    from repro.core.distributed import cyclic_layout
    from .hlo_analysis import peaks_for, roofline_terms

    dev = jax.devices()[0]
    # a roofline share is a device metric: only a TPU's own peaks apply,
    # and only to a time measured on it
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    blk_m = 128
    # pallas runs in interpret mode on CPU runners: keep its cell small
    configs = ([("vmap_l2", 16, 24, 4), ("pallas", 8, 8, 4)] if quick else
               [("vmap_l2", 32, 48, 4), ("pallas", 16, 12, 4)])
    recs = []
    for backend, m, n, csize in configs:
        rng = np.random.RandomState(n)
        A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
        V = jnp.asarray(rng.randn(m, n), jnp.float32)
        f = testfns.FUNCTIONS["rosenbrock"](n)
        for sym in (False, True):
            p = engine.plan(f, n, m=m, csize=csize, backend=backend,
                            symmetric=sym, blk_m=blk_m)
            run = p.executable("batched_hvp")
            flops, nbytes = _hlo_cost(run, A, V)
            t = _median_time(lambda r=run: r(A, V))
            bound = bound_term = pct = None
            if peaks is not None:
                terms = roofline_terms(flops, nbytes, 0.0, peaks)
                bound = terms["step_time_lower_bound_s"]
                bound_term = terms["bound"]
                pct = 100.0 * bound / t
            recs.append({
                "backend": backend, "schedule": "sym" if sym else "full",
                "m": m, "n": n, "csize": csize,
                "device": f"{dev.platform}:{dev.device_kind}",
                "cells_executed": _executed_cells(backend, m, n, csize,
                                                  blk_m, sym),
                "cells_min": num_chunk_evals(n, csize, sym),
                "flops": flops, "bytes": nbytes,
                "measured_s": t, "bound_s": bound,
                "pct_roofline": pct,
                "bound_term": bound_term,
                "status": "measured",
            })
    # cyclic sharded_rows: static sweep accounting (host-side layout); the
    # wall clock lives in benchmarks/distributed_bench.py (needs a mesh)
    n, csize, size = (24, 4, 4) if quick else (48, 4, 4)
    lay = cyclic_layout(n, csize, size)
    tri = num_chunk_evals(n, csize, True)
    recs.append({
        "backend": "sharded_rows", "schedule": "sym",
        "m": 1, "n": n, "csize": csize, "shards": size,
        "cells_executed": size * lay.executed,
        "cells_kept": int(sum(lay.kept)),
        "cells_min": tri,
        # balance bound: every shard pads to the max kept count, so the
        # total may exceed the triangle by < one block per other shard
        "cells_allowed": tri + (size - 1) * lay.block_cells_bound,
        "status": "static",
    })
    from repro.core.distributed import rows_per_shard
    nchunk = -(-n // csize)
    recs.append({
        "backend": "sharded_rows", "schedule": "full",
        "m": 1, "n": n, "csize": csize, "shards": size,
        "cells_executed": size * rows_per_shard(n, size) * nchunk,
        "cells_min": num_chunk_evals(n, csize, False),
        "status": "static",
    })
    return recs


def _sweep_gate(recs: list[dict]) -> list[str]:
    """The CI gate: symmetric schedules must not execute more chunk cells
    than the triangle bound (exact for single-device backends; cyclic
    sharded gets its documented one-block-per-shard padding slack)."""
    failures = []
    for r in recs:
        if r["schedule"] != "sym":
            continue
        allowed = r.get("cells_allowed", r["cells_min"])
        if r["cells_executed"] > allowed:
            failures.append(
                f"{r['backend']}: executed {r['cells_executed']} symmetric "
                f"chunk cells > allowed {allowed} (triangle {r['cells_min']})")
        if r.get("cells_kept", r["cells_executed"]) != r["cells_min"]:
            failures.append(
                f"{r['backend']}: kept {r.get('cells_kept')} != triangle "
                f"{r['cells_min']}")
    return failures


def _fmt_pct(r: dict) -> str:
    if r.get("status") != "measured":
        return "-"
    if r.get("pct_roofline") is None:
        return "not measured"          # timed off the chip: no roofline
    return f"{r['pct_roofline']:.2f}"


def render_curvature(recs: list[dict], md: bool = False) -> str:
    hdr = ["backend", "sched", "n", "csize", "cells", "min", "flops",
           "measured", "bound", "%roof"]
    rows = []
    for r in recs:
        rows.append([
            r["backend"], r["schedule"], r["n"], r["csize"],
            r["cells_executed"], r["cells_min"],
            f"{r['flops']:.2e}" if r.get("flops") else "-",
            _fmt_t(r["measured_s"]) if r.get("measured_s") else "-",
            _fmt_t(r["bound_s"]) if r.get("bound_s") else "-",
            _fmt_pct(r),
        ])
    widths = [max(len(str(row[i])) for row in rows + [hdr])
              for i in range(len(hdr))]

    def line(row):
        cells = [str(c).ljust(w) for c, w in zip(row, widths)]
        return ("| " + " | ".join(cells) + " |") if md else "  ".join(cells)

    out = [line(hdr)]
    if md:
        out.append("|" + "|".join("-" * (w + 2) for w in widths) + "|")
    out += [line(r) for r in rows]
    # per-backend symmetric-vs-full wall-clock speedup
    by = {}
    for r in recs:
        if r.get("measured_s"):
            by.setdefault(r["backend"], {})[r["schedule"]] = r["measured_s"]
    for b, d in sorted(by.items()):
        if "sym" in d and "full" in d:
            out.append(f"\n{b}: symmetric-vs-full wall-clock speedup = "
                       f"{d['full'] / d['sym']:.2f}x")
    return "\n".join(out)


def run_curvature(quick: bool = False, md: bool = False,
                  out: str | None = None,
                  json_out: str | None = None) -> int:
    recs = curvature_records(quick=quick)
    table = render_curvature(recs, md=md)
    print(table)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as fh:
            fh.write(table + "\n")
    if json_out:
        os.makedirs(os.path.dirname(json_out) or ".", exist_ok=True)
        with open(json_out, "w") as fh:
            json.dump(recs, fh, indent=2)
    failures = _sweep_gate(recs)
    for msg in failures:
        print("SWEEP-GATE FAIL:", msg)
    if not failures:
        print("\nsweep gate: all symmetric schedules within the triangle "
              "bound")
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=DEFAULT_DIR)
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--curvature", action="store_true",
                    help="measure the curvature backends instead of "
                         "reading dry-run records")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None, help="write the table here")
    ap.add_argument("--json", default=None, help="write raw records here")
    args = ap.parse_args()
    if args.curvature:
        raise SystemExit(run_curvature(quick=args.quick, md=args.md,
                                       out=args.out, json_out=args.json))
    recs = load_records(args.dir)
    print(render_table(recs, args.md))
    ok = [r for r in recs if r["status"] == "ok"]
    if ok:
        worst = min(ok, key=lambda r: r.get("useful_flop_ratio") or 1e9)
        coll = max(ok, key=lambda r: r["roofline"]["collective_s"]
                   / max(r["roofline"]["step_time_lower_bound_s"], 1e-30))
        print(f"\nworst useful-FLOP ratio : {worst['cell']}"
              f" ({worst.get('useful_flop_ratio'):.3f})")
        print(f"most collective-bound   : {coll['cell']}")


if __name__ == "__main__":
    main()
