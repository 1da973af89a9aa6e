"""Rescaling: ``StreamEngine.reconfigure`` alternating between the
configuration deployed and the traffic's other one, with
``ticks_between`` ticks after each, so that every task's store holds
consolidated levels, tiers and runs again before the next snapshot.

``rescale_s`` is the wall time of the ``reconfigure`` calls alone, over
their number; the window always ends on an even number of calls, so both
directions count alike.  After each call every task's state is taken
through its snapshot (outside the timed call) and checked afterwards.
"""
from __future__ import annotations

import time

from bench.check import Check
from bench.deploy import Deployment


def _config(run) -> tuple[int, int]:
    node = run.dep.engine.flow.nodes[run.dep.op_name]
    return (node.parallelism, node.memory_level)


def setup(run) -> None:
    run.dep = Deployment(run.config, run.traffic, run.seed,
                         run.cell.reference)
    run.dep.recorder.mark("parallelism", _config(run)[0])
    for _ in range(int(run.traffic["warm_ticks"])):
        run.dep.tick()


def step(run) -> float:
    """One unit of the window's work: a ``reconfigure`` to the other
    configuration, its state taken, and the ticks after it.  Returns the
    wall seconds of the ``reconfigure`` call."""
    dep = run.dep
    a, b = (tuple(c) for c in run.traffic["alternate"])
    target = b if _config(run) == a else a
    t = time.perf_counter()
    dep.engine.reconfigure({dep.op_name: target})
    took = time.perf_counter() - t
    dep.recorder.mark("state", dep.task_snapshots())
    dep.recorder.mark("parallelism", target[0])
    for _ in range(int(run.traffic["ticks_between"])):
        dep.tick()
    return took


def window(run) -> dict:
    times = []
    t0 = time.perf_counter()
    while (not times or len(times) % 2
           or time.perf_counter() - t0 < run.seconds):
        times.append(step(run))
    run.attempted = len(times)
    run.notes.update(rescales=len(times), window_s=time.perf_counter() - t0,
                     rescale_times_s=times)
    return {"rescale_s": sum(times) / len(times)}


def collect(run) -> None:
    """Every rescale's state was taken inside the window."""


def check(run, weights: str = "exact") -> Check:
    c = Check(run.dep, weights)
    c.replay(run.dep.recorder.log)
    c.routing_keys(run.dep.recorder.log)
    return c
