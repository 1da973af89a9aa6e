"""Tiny cells for the CPU: the cells of ``BENCHMARK.json`` cut to a few
thousand keys and events, run with the kernels in the Pallas
interpreter and without the harness's look for a chip."""
import copy
import dataclasses
import pathlib
import time

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY = {
    "q8.steady": {"keyspace": 2000, "parallelism": 3, "rate": 3000},
    "q11.steady": {"keyspace": 2000, "parallelism": 3, "rate": 3000,
                   "hot_keys": 300},
    "q8.rescale": {"keyspace": 2000, "parallelism": 3, "rate": 3000,
                   "alternate": [[2, 1], [3, 1]]},
}


def tiny_cell(workload: str, warm_ticks: int = 2) -> harness.Cell:
    cell = harness.resolve(ROOT, workload)
    t = TINY[workload]
    cfg, traffic = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    cfg["keyspace"] = t["keyspace"]
    cfg["deployment"]["parallelism"] = t["parallelism"]
    if "hot_keys" in t:
        cfg["stream"]["keys"]["hot_keys"] = t["hot_keys"]
    traffic["rate_events_per_s"] = t["rate"]
    if "history" in cfg:
        cfg["history"]["events_per_s"] = t["rate"]
    traffic["warm_ticks"] = warm_ticks
    if "alternate" in t:
        traffic["alternate"] = t["alternate"]
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def run_tiny(workload: str, seed: int = 7, trace: bool = False,
             weights: str = "exact", warm_ticks: int = 2) -> dict:
    run = harness.measure(tiny_cell(workload, warm_ticks), seed, 0.0, trace,
                          t_start=time.monotonic(), require_chip=False,
                          kernel_impl="interpret")
    return harness.judge(run, weights)


@pytest.fixture(params=sorted(TINY))
def workload(request):
    return request.param
