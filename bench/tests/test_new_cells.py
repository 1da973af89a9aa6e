"""CPU rehearsal of the ``steady_keyed`` cells at a tiny size, with the
kernels in the Pallas interpreter: ``q5.steady`` (the hopping-window
count, its combiner and the watermark-closed hot items) and ``q8.p1``
(the window join in one task).  Each is correct on the system as it is;
``q5.steady`` reads not correct against the ``per_batch`` control and
with a combiner that forwards wrong rows, and each cell reads not correct
when part of a batch on the way to its operator is lost or sent twice."""
import copy
import dataclasses
import time

import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import ROOT
from repro.streaming.engine import StreamEngine
from repro.streaming.operators import WindowAggOp

TINY = {
    "q5.steady": {"rate_events_per_s": 3000, "warm_ticks": 6,
                  "deployment": {"parallelism": 3}},
    "q8.p1": {"rate_events_per_s": 3000, "warm_ticks": 2,
              "keyspace": 2000},
}


def tiny_cell(workload: str) -> harness.Cell:
    cell = harness.resolve(ROOT, workload)
    cfg, traffic = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    t = TINY[workload]
    for key in ("rate_events_per_s", "warm_ticks"):
        traffic[key] = t[key]
    cfg["deployment"].update(t.get("deployment", {}))
    cfg["keyspace"] = t.get("keyspace", cfg["keyspace"])
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def measure(workload: str, seed: int = 7):
    return harness.measure(tiny_cell(workload), seed, 0.0, False,
                           t_start=time.monotonic(), require_chip=False,
                           kernel_impl="interpret")


@pytest.fixture(scope="module")
def q5_run():
    return measure("q5.steady")


def test_q5_cell_is_correct(q5_run):
    res = harness.judge(q5_run)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # hot items were emitted, and so compared
    assert any(len(rows[0]) for _, _, rows in q5_run.dep.emitted.log)


def test_q5_per_batch_control_is_not_correct(q5_run):
    res = harness.judge(q5_run, "per_batch")
    assert not res["correct"]
    assert res["checks"]["state_mismatch"]["value"] > 0


def test_q8_p1_cell_is_correct():
    run = measure("q8.p1")
    assert ("parallelism", 1) in run.dep.recorder.log   # the traffic's
    res = harness.judge(run)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_broken_combiner_is_not_correct(monkeypatch):
    process = WindowAggOp.process

    def one_below(self, state, batch):
        out = process(self, state, batch)
        value = out.value.copy()
        value[:, 1] = np.maximum(value[:, 1] - 1, 0)
        return dataclasses.replace(out, value=value)
    monkeypatch.setattr(WindowAggOp, "process", one_below)
    res = harness.judge(measure("q5.steady"))
    assert not res["correct"]
    assert res["checks"]["output_mismatch"]["value"] > 0


@pytest.mark.parametrize("workload, sender", [("q5.steady", "key_by_auction"),
                                              ("q8.p1", "source")])
@pytest.mark.parametrize("fault", ["lost", "twice"])
def test_lost_or_repeated_events_are_not_correct(monkeypatch, workload,
                                                 sender, fault):
    """The sender's second batch towards the cell's operator loses its
    second half, or goes out twice."""
    emit, sent = StreamEngine._emit, []

    def faulty(self, name, out):
        if name == sender and len(out):
            sent.append(len(out))
            if len(sent) == 2:
                if fault == "lost":
                    return emit(self, name, out.slice(0, len(out) // 2))
                emit(self, name, out)
        return emit(self, name, out)
    monkeypatch.setattr(StreamEngine, "_emit", faulty)
    res = harness.judge(measure(workload))
    assert len(sent) > 2
    assert not res["correct"]
    assert res["checks"]["routing_mismatch"]["value"] > 0
