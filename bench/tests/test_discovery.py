"""A configuration, a traffic mix, a driver kind, a reference and a
per-layer metric are found by name from new files alone: a checkout with
files added and none of the harness's edited."""
import json
import shutil
import time

from bench import harness
from bench.tests.conftest import ROOT

DRIVER = '''
from types import SimpleNamespace


def setup(run):
    run.notes["level"] = run.config["level"]


def window(run):
    run.attempted = run.traffic["ops"]
    return {"ops_per_s": run.traffic["ops"] / max(run.seconds, 1e-9)}


def collect(run):
    pass


def check(run, weights="exact"):
    return SimpleNamespace(counts={"toy_mismatch": 0})
'''
METRIC = '''
def read(run):
    return 42.0 if run.hooks is not None else None
'''
REFERENCE = '''
def initial_state(config, rng):
    return None
'''


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "bench"
    (b / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "level": 3, "deployment": {"semantics": "toy_sem"}}))
    (b / "traffic" / "toy-load.json").write_text(json.dumps(
        {"driver": "toy_driver", "ops": 500}))
    (b / "drivers" / "toy_driver.py").write_text(DRIVER)
    (b / "metrics" / "toy_metric.py").write_text(METRIC)
    (b / "references" / "toy_sem.py").write_text(REFERENCE)
    spec["configs"].append({"name": "toy", "source": "a test",
                            "file": "bench/configs/toy.json",
                            "reduced": [], "why": "discovery"})
    spec["workloads"].append({"name": "toy.cell", "config": "toy",
                              "traffic": "toy-load", "chips": 1,
                              "why": "discovery"})
    spec["end_to_end"].append({"name": "ops_per_s", "unit": "ops/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["toy.cell"]})
    spec["per_layer"].append({"name": "toy_metric", "unit": "%",
                              "better": "lower", "source": "host_clock",
                              "layer": "toy", "moves": "ops_per_s",
                              "workloads": ["toy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_new_files_are_found_by_name(tmp_path):
    cell = harness.resolve(_checkout(tmp_path), "toy.cell")
    assert cell.config["level"] == 3 and cell.traffic["ops"] == 500
    assert cell.driver.__file__.endswith("toy_driver.py")
    assert cell.reference.__file__.endswith("toy_sem.py")
    assert [m["name"] for m, _ in cell.per_layer] == ["toy_metric"]
    assert {m["name"] for m in cell.end_to_end} == {"ops_per_s", "setup_s"}


def test_new_cell_runs_through_the_harness(tmp_path):
    cell = harness.resolve(_checkout(tmp_path), "toy.cell")
    kw = dict(t_start=time.monotonic(), require_chip=False,
              kernel_impl="numpy")
    plain = harness.run_cell(cell, 1, 2.0, False, **kw)
    assert plain["correct"] and plain["attempted"] == 500
    assert plain["metrics"]["ops_per_s"]["value"] == 250.0
    assert set(plain["metrics"]) == {"ops_per_s", "setup_s"}
    traced = harness.run_cell(cell, 1, 2.0, True, **kw)
    assert traced["metrics"] == {"toy_metric": {"value": 42.0, "unit": "%"}}


def test_existing_cells_resolve():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.resolve(ROOT, w["name"])
        assert cell.per_layer and cell.end_to_end
