"""BENCHMARK.json against the benchmark's contract, the result line's
schema, and discovery of configurations, traffic and metrics by name."""

import json
import os
import re
import shutil

import pytest

from bench_helpers import ROOT, SMALL_M, run, run_small

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = run.load_benchmark()
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:3] == ["python3", "-m", "bench.run"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            spec = json.load(f)
        assert spec["name"] == c["name"]
        assert spec["reduced"] == c["reduced"] == []
        assert os.path.isfile(os.path.splitext(
            os.path.join(ROOT, c["file"]))[0] + ".py")


def test_workloads_find_their_files():
    seen = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert w["chips"] == 1
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        traffic = run.load_traffic(w["traffic"])
        assert run.load_driver(traffic).__name__ == "Driver"
        run.load_config(BENCH, w["config"])


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= moved
        assert callable(run.load_reader(m["name"]))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in run.metrics_of(BENCH, w["name"],
                                                 "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_of(BENCH, w["name"], "per_layer")


def test_check_budget_fits():
    cells = 24
    runs = 2 + 14 * cells
    need = runs * (BENCH["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert need <= 43200


def test_result_line_schema():
    res = run_small("rosenbrock.batch-paper", seed=2 ** 33 + 7)
    assert list(res)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"points_per_s", "setup_s"}
    for m in res["metrics"].values():
        assert set(m) >= {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for name, c in res["compared"].items():
        assert c["value"] <= c["limit"], name
    json.dumps(res)


def test_a_new_cell_needs_only_new_files_and_an_entry(tmp_path):
    """A copy of the benchmark, with one traffic file, one metric reader
    and two entries added to BENCHMARK.json, runs the new cell."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"))
    with open(os.path.join(root, "bench", "traffic", "batch-one-set.json"),
              "w") as f:
        json.dump({"driver": "batch", "input_sets": 1, "a_low": -1.0,
                   "a_high": 1.0}, f)
    with open(os.path.join(root, "bench", "metrics", "calls_made.py"),
              "w") as f:
        f.write("def read(run):\n    return run.counters['calls']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "rosenbrock.batch-one-set",
                               "config": "rosenbrock",
                               "traffic": "batch-one-set", "chips": 1,
                               "why": "a test cell"})
    bench["per_layer"].append({"name": "calls_made", "unit": "calls",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "kernels", "moves": "points_per_s",
                               "workloads": ["rosenbrock.batch-one-set"]})
    for m in bench["end_to_end"]:
        if m["name"] == "points_per_s":
            m["workloads"].append("rosenbrock.batch-one-set")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    res = run.run_cell("rosenbrock.batch-one-set", 5, 0.3, True, root=root,
                       on_chip=False, config_override={"m": SMALL_M})
    assert res["correct"] is True
    assert res["metrics"]["calls_made"]["value"] >= 1
    assert res["metrics"]["calls_made"]["unit"] == "calls"


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        run.find_workload(BENCH, "no.such-cell")

