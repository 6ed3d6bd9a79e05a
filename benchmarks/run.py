"""Benchmark orchestrator: one module per paper table/figure.

  python -m benchmarks.run [--quick] [--only seq,levels,...]

Emits ``name,value,derived`` CSV; EXPERIMENTS.md quotes these. Paper-claim
assertions (orderings, argmin placement) live in the modules and raise on
violation.
"""

from __future__ import annotations

import argparse
import sys
import time

SUITES = {
    "opcount": "benchmarks.opcount",        # §5 analysis + jaxpr validation
    "seq": "benchmarks.seq_trends",         # Figs 3-9
    "levels": "benchmarks.gpu_levels",      # Figs 10-12, Tables 1-3
    "csize": "benchmarks.csize_sweep",      # §3.2 dial
    "kernel": "benchmarks.kernel_bench",    # Pallas layer
    "optimizer": "benchmarks.optimizer_compare",  # SophiaH/CHESSFAD vs AdamW
    "engine": "benchmarks.engine_bench",    # plan/execute csize selection
    "service": "benchmarks.service_bench",  # async coalescing throughput
    "selftune": "benchmarks.selftune_bench",  # online bucket-aware autotune
    "distributed": "benchmarks.distributed_bench",  # L1 rows vs mesh shape
    "zoo": "benchmarks.zoo_bench",          # pytree workloads on zoo configs
    "frontend": "benchmarks.frontend_bench",  # serving stack: cross-n + TCP
    "obs": "benchmarks.obs_bench",          # observability overhead gates
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a jax profiler session of the whole run "
                         "into DIR (view with TensorBoard or Perfetto); "
                         "device executions are annotated per bucket")
    args = ap.parse_args()
    names = list(SUITES) if not args.only else args.only.split(",")

    from contextlib import nullcontext

    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    session = (obs.profile_session(args.profile) if args.profile
               else nullcontext())
    print("name,value,derived")
    with session:
        for name in names:
            mod = __import__(SUITES[name], fromlist=["main"])
            t0 = time.time()
            mod.main(quick=args.quick)
            print(f"# suite {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)


if __name__ == "__main__":
    main()
