"""Run one cell of BENCHMARK.json once, and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (start, inputs from the seed, every shape the cell uses compiled
and run once) is timed from process start to the window's opening.  The
window then runs the cell's traffic for ``--seconds``.  With ``--trace 1``
the window runs under the profiler and the line carries the cell's
per-layer metrics; with ``--trace 0`` its end-to-end metrics.  After the
window the device's peak memory is read, the program's state is freed, and
what the window produced is compared with the reference.  The numbers
compared are printed beside their limits, last on standard error and last
in the result line.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 3.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NO_CHIP_EXIT = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


# -- finding things by name ---------------------------------------------------

@dataclass
class Config:
    name: str
    spec: dict          # bench/configs/<name>.json
    module: object      # bench/configs/<name>.py


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in bench['workloads']]}")


def load_config(bench: dict, name: str, root: str = ROOT) -> Config:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    path = os.path.join(root, entry["file"])
    with open(path) as f:
        spec = json.load(f)
    module = _load_module(os.path.splitext(path)[0] + ".py",
                          f"bench_config_{name}")
    return Config(name, spec, module)


def load_traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def load_driver(traffic: dict):
    return importlib.import_module(f"bench.{traffic['driver']}").Driver


def load_reader(metric: str, root: str = ROOT):
    return _load_module(os.path.join(root, "bench", "metrics", f"{metric}.py"),
                        f"bench_metric_{metric}").read


def metrics_of(bench: dict, workload: str, kind: str) -> list:
    """The cell's end_to_end or per_layer metrics.  A metric with a
    ``workloads`` key belongs to the cells it lists; one without it, to
    every cell that reports the end-to-end metric it moves (or, for an
    end-to-end metric, to every cell)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and ("workloads" in m or m["moves"] in names)]


# -- the run ------------------------------------------------------------------

@dataclass
class RunView:
    """What a per-layer reader may read."""
    workload: dict
    config: Config
    counters: dict
    timeline: object        # bench.trace.Timeline
    window_ns: tuple        # the traced window on the timeline's clock
    device_kind: str


def _devices_or_none(chips: int, on_chip: bool):
    import jax
    devs = jax.devices()
    if on_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        return None
    return devs[:chips]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, on_chip: bool = True,
             config_override: dict | None = None,
             traffic_override: dict | None = None):
    """One run; returns the result dict, or None where no chip was found.

    ``on_chip=False`` and the overrides are for the CPU tests, which drive
    the harness without a chip, without the persistent compile cache and
    at sizes a test can hold; a run of the benchmark never passes them."""
    bench = load_benchmark(root)
    cell = find_workload(bench, workload)
    config = load_config(bench, cell["config"], root)
    config.spec.update(config_override or {})
    traffic = {**load_traffic(cell["traffic"], root),
               **(traffic_override or {})}

    import jax
    from . import trace as tr
    from .common import memory_peak_bytes

    devices = _devices_or_none(int(cell["chips"]), on_chip)
    if devices is None:
        found = jax.devices()
        print(f"bench: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(found)} {found[0].platform} device(s)",
              file=sys.stderr)
        return None
    if on_chip:
        _enable_compile_cache(root)
    events = _EventCounter()
    jax.monitoring.register_event_listener(events.on_event)
    jax.monitoring.register_event_duration_secs_listener(events.on_duration)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        driver = load_driver(traffic)(config, traffic, seed)
        setup_events = dict(events.counts)
        setup_s = process_age_s()
        if trace:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
        window_s = driver.window(seconds)
        if trace:
            jax.profiler.stop_trace()
        window_compiles = events.counts.get(COMPILE_EVENT, 0) \
            - setup_events.get(COMPILE_EVENT, 0)
        peak = memory_peak_bytes(devices)
        e2e = driver.end_to_end()
        counters = {**driver.counters(), "window_compiles": window_compiles}
        driver.release()
        checked = driver.check()

        result = {"correct": checked.correct, "attempted": checked.attempted,
                  "failed": checked.failed}
        dev = devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        metrics = {}
        if trace:
            timeline = tr.load(trace_dir)
            lo, hi = timeline.spans_named("bench:window")[0]
            view = RunView(cell, config, counters, timeline, (lo, hi),
                           dev.device_kind)
            for m in metrics_of(bench, workload, "per_layer"):
                got = load_reader(m["name"], root)(view)
                if got is None:
                    continue
                if not isinstance(got, dict):
                    got = {"value": got}
                metrics[m["name"]] = {"value": got.pop("value"),
                                      "unit": m["unit"], **got}
            ops = [op for ops in timeline.ops.values() for op in ops]
            busy = [tr.busy_ns(o, lo, hi) for o in timeline.ops.values()]
            device["busy_s"] = (sum(busy) / len(busy) * 1e-9) if busy else 0.0
            device["window_s"] = (hi - lo) * 1e-9
            result["breakdown"] = {
                "device_ops": [list(x) for x in
                               tr.top_ops(tr.within(ops, [(lo, hi)]))],
                "idle_gaps": [list(x) for x in tr.name_gaps(
                    max(timeline.ops.values(), key=len, default=[]),
                    timeline.host, lo, hi)]}
        else:
            values = {**e2e, "setup_s": setup_s}
            for m in metrics_of(bench, workload, "end_to_end"):
                if m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["info"] = {"window_s": window_s, "setup_s": setup_s,
                          "setup_compile_events": setup_events,
                          **{k: v for k, v in counters.items()
                             if not isinstance(v, list)}}
        result["compared"] = {k: {"value": v, "limit": lim}
                              for k, v, lim in ((k, *vl) for k, vl in
                                                checked.numbers.items())}
        result["compared"]["failed"] = {"value": checked.failed, "limit": 0}
        return result
    finally:
        jax.monitoring.unregister_event_listener(events.on_event)
        jax.monitoring.unregister_event_duration_listener(events.on_duration)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _profile_options():
    """Device ops and host annotations, without JAX's default Python
    tracer: that one records every Python call of every thread, which
    slows the host-bound served path to a third of its untraced rate."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class _EventCounter:
    def __init__(self):
        self.counts: dict = {}

    def on_event(self, event: str, **_kw) -> None:
        self.counts[event] = self.counts.get(event, 0) + 1

    def on_duration(self, event: str, _secs: float, **_kw) -> None:
        self.counts[event] = self.counts.get(event, 0) + 1


def _enable_compile_cache(root: str) -> None:
    """JAX's persistent cache at a fixed directory inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR points), for every program."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _prepare_environment(root: str) -> None:
    sys.path.insert(0, os.path.join(root, "src"))
    # no stored autotune winner may steer a run: persistence off
    os.environ["REPRO_AUTOTUNE_CACHE"] = ""
    # the reference runs on the host CPU beside the chip
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _prepare_environment(ROOT)
    import repro  # noqa: F401  -- the system under test must be here
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    if result is None:
        return NO_CHIP_EXIT
    compared = result.pop("compared")
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result["compared"] = compared        # last key of the line
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
