"""A model configuration cut to one chip's share enters the benchmark as
new files and entries only.  A copy of the benchmark gains a configuration
cut in depth (``reduced``, ``published``, ``deployment``), a new driver
with its own check, control and test size, a traffic file, two per-layer
readers and their entries, and nothing in it is edited.  The copy passes
the schema tests, runs the new cell with ``correct`` true, gives its
control a reading above the cell's limit, and takes a four-chip entry."""

import json
import os
import shutil

from bench_helpers import ROOT, run, run_small
from bench import control, peaks
from bench import trace as tr

import test_bench_schema as schema

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "new_cell")
CELL = "toy_cut.probes"


def _copy_with_new_cell(root: str) -> dict:
    for path in run.load_benchmark()["paths"]:
        shutil.copytree(os.path.join(ROOT, path), os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for dirpath, _dirs, files in os.walk(FIXTURE):
        for name in files:
            src = os.path.join(dirpath, name)
            dst = os.path.join(root, os.path.relpath(src, FIXTURE))
            assert not os.path.exists(dst), f"{dst} is not a new file"
            shutil.copy(src, dst)
    bench = run.load_benchmark(root)
    bench["configs"].append({
        "name": "toy_cut", "source": "a test configuration",
        "file": "bench/configs/toy_cut.json", "reduced": ["num_layers"],
        "why": "a stack of tanh layers, 2 of its 8 layers: one pipeline "
               "stage on this chip"})
    bench["workloads"].append({
        "name": CELL, "config": "toy_cut", "traffic": "probes", "chips": 1,
        "why": "pytree HVPs of the loss over the batch, back to back"})
    for name, source, layer in (("toy_hvp_roofline", "device_trace",
                                 "kernels"),
                                ("toy_step_mfu", "device_trace", "device")):
        bench["per_layer"].append({
            "name": name, "unit": "%", "better": "higher", "source": source,
            "layer": layer, "moves": "points_per_s", "workloads": [CELL]})
    for m in bench["end_to_end"]:
        if m["name"] == "points_per_s":
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench


def _device_ops_from_calls(real_load):
    """The CPU backend's trace has no device plane: lay each call's span in
    as the work of one device, so that the readers' arithmetic runs."""
    def load(log_dir):
        tl = real_load(log_dir)
        tl.ops["/device:TPU:0"] = [(n, t0, t1) for n, t0, t1 in tl.host
                                   if n == "bench:toy_hvp"]
        return tl
    return load


def test_a_cut_model_cell_needs_only_new_files_and_entries(tmp_path,
                                                           monkeypatch):
    root = str(tmp_path)
    bench = _copy_with_new_cell(root)

    schema.test_top_level_keys(bench, root)
    schema.test_configs(bench, root)
    schema.test_workloads_find_their_files(bench, root)
    schema.test_metrics(bench, root)
    schema.test_every_cell_reports_setup_another_e2e_and_a_layer(bench)
    config = run.load_config(bench, "toy_cut", root)
    assert config.spec["reduced"] == ["num_layers"]

    # the CPU has no published peaks and no device plane: lend it the
    # v5e's peaks, and its own call spans as device work (no device number)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(tr, "load", _device_ops_from_calls(tr.load))
    res = run_small(CELL, seed=2 ** 33 + 11, seconds=0.3, trace=True,
                    root=root)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"toy_hvp_roofline", "toy_step_mfu"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"] == "%"
    (value, limit), = [(c["value"], c["limit"]) for k, c in
                       res["compared"].items() if k != "failed"]
    assert value < limit / 10

    small = run.driver_module(run.load_traffic("probes", root), root).SMALL
    got = control.control_reading(CELL, 2 ** 33 + 11, root=root,
                                  config_override=small["config"],
                                  traffic_override=small["traffic"])
    assert got["value"] > 3 * got["limit"]

    run.check_chips(bench["workloads"] + [
        {**bench["workloads"][-1], "name": "toy_cut.probes-4chip",
         "chips": 4}])
