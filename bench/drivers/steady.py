"""Steady processing: ``StreamEngine.run_tick`` back to back.

The rate is in sim time; in wall time this is a closed loop, since the
next tick starts when the last one ends, so the events the stateful
operator processed over the window's wall seconds are the rate the
system sustains.  The window runs whole ticks until ``seconds`` have
passed, and the rate is taken over all of them and all their time.  The
traffic file names the end-to-end metric the rate is reported as
(``metric``).
"""
from __future__ import annotations

import time

import numpy as np

from bench.check import Check
from bench.deploy import Deployment


def setup(run) -> None:
    run.dep = Deployment(run.config, run.traffic, run.seed,
                         run.cell.reference)
    for _ in range(int(run.traffic["warm_ticks"])):
        run.dep.tick()


def step(run) -> None:
    """One unit of the window's work: a tick."""
    run.dep.tick()


def window(run) -> dict:
    dep = run.dep
    events0, batches0 = dep.recorder.events, len(dep.gen.emitted)
    ends = []
    t0 = time.perf_counter()
    while True:
        step(run)
        ends.append(time.perf_counter() - t0)
        elapsed = ends[-1]
        if elapsed >= run.seconds:
            break
    run.attempted = sum(len(ev[0]) for ev in dep.gen.emitted[batches0:])
    tick_s = np.diff(np.r_[0.0, ends])
    run.notes.update(ticks=len(ends), window_s=elapsed,
                     events=dep.recorder.events - events0,
                     tick_s_quartiles=np.quantile(tick_s, [0.25, 0.5, 0.75])
                     .round(4).tolist())
    return {run.traffic["metric"]: (dep.recorder.events - events0) / elapsed}


def collect(run) -> None:
    """The state every task holds after the window (through the store's
    own snapshot, which consolidates on the device)."""
    run.dep.recorder.mark("state", run.dep.task_snapshots())


def check(run, weights: str = "exact") -> Check:
    dep = run.dep
    c = Check(dep, weights)
    c.replay(dep.recorder.log)
    c.routing_prefix(dep.recorder.log, dep.gen.emitted,
                     int(dep.config["deployment"]["parallelism"]))
    return c
