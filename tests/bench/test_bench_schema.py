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


# The schema tests take the benchmark and its root as defaults, so that a
# test of a copy (tests/bench/test_bench_new_cell.py) runs them on it.

def test_top_level_keys(bench=BENCH, root=ROOT):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:3] == ["python3", "-m", "bench.run"]
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(root, p))
    assert len(json.dumps(bench)) < 64 * 1024


def test_configs(bench=BENCH, root=ROOT):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        with open(os.path.join(root, c["file"])) as f:
            spec = json.load(f)
        assert spec["name"] == c["name"]
        run.check_config(c, spec)
        assert spec["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.splitext(
            os.path.join(root, c["file"]))[0] + ".py")


def test_workloads_find_their_files(bench=BENCH, root=ROOT):
    seen = set()
    run.check_chips(bench["workloads"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        traffic = run.load_traffic(w["traffic"], root)
        assert run.load_driver(traffic, root).__name__ == "Driver"
        run.load_config(bench, w["config"], root)


def test_metrics(bench=BENCH, root=ROOT):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= moved
        assert callable(run.load_reader(m["name"], root))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_every_cell_reports_setup_another_e2e_and_a_layer(bench=BENCH):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in run.metrics_of(bench, w["name"],
                                                 "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_of(bench, w["name"], "per_layer")


def _cut(entry_reduced=("num_layers",), **over):
    """The entry and the file of a configuration cut in depth, with
    ``over`` laid over the file."""
    spec = {"name": "cut", "num_layers": 2, "hidden_size": 64,
            "head_dim": 16, "reduced": ["num_layers"],
            "published": {"num_layers": 8, "head_dim": 32},
            "deployment": "each layer whole on one chip; the 8 layers as "
                          "4 pipeline stages of 2, this chip holds one",
            "assumed": ["hidden_size"], **over}
    return {"name": "cut", "reduced": list(entry_reduced)}, spec


def _existing(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def _cells(*chips):
    return ([{"name": f"c{i}", "chips": c} for i, c in enumerate(chips)],)


# case -> (contract function, its arguments, whether it refuses them)
CONTRACT = {
    "no-published-value": (run.check_config,
                           lambda: _cut(published={}), True),
    "published-as-held": (run.check_config,
                          lambda: _cut(published={"num_layers": 2}), True),
    "reduced-differs-from-file": (run.check_config,
                                  lambda: _cut(entry_reduced=()), True),
    "cut-without-deployment": (run.check_config,
                               lambda: _cut(deployment=None), True),
    "width-cut": (run.check_config,
                  lambda: _cut(("head_dim",), reduced=["head_dim"]), True),
    "5-cells-3-on-4-chips": (run.check_chips,
                             lambda: _cells(1, 4, 1, 4, 4), True),
    "cut-as-stated": (run.check_config, _cut, False),
    "1-cell-on-4-chips": (run.check_chips, lambda: _cells(4), False),
    "4-cells-2-on-4-chips": (run.check_chips, lambda: _cells(1, 4, 4, 1),
                             False),
    "rosenbrock": (run.check_config, lambda: _existing("rosenbrock"),
                   False),
    "fletcher_powell": (run.check_config,
                        lambda: _existing("fletcher_powell"), False),
}


@pytest.mark.parametrize("case", list(CONTRACT))
def test_cut_and_chip_contract(case):
    check, args, refused = CONTRACT[case]
    if refused:
        with pytest.raises(ValueError):
            check(*args())
    else:
        check(*args())


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
                   "a_high": 1.0, "lead_s": 0.05, "lead_bytes": 1e8,
                   "checks": 10**6}, f)
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

