"""Steady processing, as ``steady.py`` (ticks back to back, the rate over
the window's wall seconds), for cells whose stateful operator is fed
through a keyed exchange and may feed a second stateful stage.

Beyond ``steady``'s, the traffic file may give:

* ``parallelism``: ``[tasks, memory level]`` of the operator, in place
  of the configuration's;
* ``downstream``: the name of an event-time operator after it, whose
  rows (emitted when the engine's watermark reaches a task) are
  recorded and compared with the reference's ``closed`` rows for the
  same span of event time, counted in ``output_mismatch``.

Routing, counted in ``routing_mismatch``: an operator the source feeds
directly must have processed, in order, a prefix of what the
partitioning sends each task (``Check.routing_prefix``); otherwise every
processed event's key must belong to the task that processed it
(``Check.routing_keys``).  And what the operator processed, with what is
still queued at it and on the way to it, must be every event the source
emitted, each once: as emitted, or as the reference's ``route`` sends it
through the operators between.

In the traced run the program's spans are on for the window; every run
keeps the window's change of the program's counters in
``run.notes["counts"]``.
"""
from __future__ import annotations

import copy

import numpy as np

from bench.check import Check, _rows, hash_partition, multiset_difference
from bench.deploy import Deployment
from bench.drivers import steady


class Emitted:
    """Wraps an event-time operator's ``on_watermark``: every call's
    task, watermark and output rows, in order."""

    def __init__(self, engine, op_name: str):
        self.engine, self.op_name = engine, op_name
        op = engine.flow.nodes[op_name].op
        self._fire = op.on_watermark
        op.on_watermark = self._record
        self.tasks = len(engine.tasks[op_name])
        self.log: list[tuple] = []

    def _record(self, state, watermark):
        out = self._fire(state, watermark)
        task = next(i for i, t in enumerate(self.engine.tasks[self.op_name])
                    if t.state is state)
        self.log.append((task, watermark,
                         (out.key, out.value, out.ts, out.kind)))
        return out


def _between(flow, op_name: str) -> list[str]:
    """The operators on the way from the sources to ``op_name``."""
    sources, seen, todo = set(flow.sources()), [], [op_name]
    while todo:
        for u in flow.upstream(todo.pop()):
            if u not in sources and u not in seen:
                seen.append(u)
                todo.append(u)
    return seen


def setup(run) -> None:
    config = run.config
    if "parallelism" in run.traffic:
        config = copy.deepcopy(config)
        p, level = run.traffic["parallelism"]
        config["deployment"].update(parallelism=int(p),
                                    memory_level=int(level))
    run.dep = dep = Deployment(config, run.traffic, run.seed,
                               run.cell.reference)
    dep.recorder.mark("parallelism", int(config["deployment"]["parallelism"]))
    dep.between = _between(dep.engine.flow, dep.op_name)
    dep.emitted = None
    if "downstream" in run.traffic:
        dep.emitted = Emitted(dep.engine, run.traffic["downstream"])
    for _ in range(int(run.traffic["warm_ticks"])):
        dep.tick()


def step(run) -> None:
    """One unit of the window's work: a tick."""
    run.dep.tick()


def window(run) -> dict:
    from repro import obs
    before = obs.counts.copy()
    if run.trace:
        obs.enable(True)
    try:
        values = steady.window(run)
    finally:
        if run.trace:
            obs.enable(False)
    run.notes["counts"] = dict(obs.counts - before)
    return values


def collect(run) -> None:
    """``steady``'s, and the batches still queued at the operator and on
    the way to it (references: batches are immutable)."""
    dep = run.dep
    steady.collect(run)
    tasks = dep.engine.tasks
    dep.queued = [b for t in tasks[dep.op_name] for b in t.queue]
    dep.queued_upstream = [b for name in dep.between
                           for t in tasks[name] for b in t.queue]
    if dep.emitted is not None:
        dep.emitted.engine = None


def _cat(batches: list[tuple]) -> tuple:
    return tuple(np.concatenate(c) for c in zip(*batches))


def _row_hash(rows: np.ndarray) -> np.ndarray:
    h = np.zeros(len(rows), np.uint64)
    for col in rows.T:
        h = (h ^ col.view(np.uint64)) * np.uint64(0x9E3779B97F4A7C15)
        h ^= h >> np.uint64(29)
    return h


def exactly_once_difference(got: tuple, want: tuple) -> int:
    """``multiset_difference`` of two event sets, ordered first by a hash
    of each row so that only the rows that then disagree are counted
    exactly (rows equal at the same place cancel)."""
    ra, rb = _rows(got), _rows(want)
    if len(ra) != len(rb):
        return multiset_difference(got, want)
    ra = ra[np.argsort(_row_hash(ra))]
    rb = rb[np.argsort(_row_hash(rb))]
    bad = (ra != rb).any(axis=1)
    if not bad.any():
        return 0
    a, b = ra[bad], rb[bad]
    split = lambda r: (r[:, 0], r[:, 3:].astype(np.int32),   # noqa: E731
                       r[:, 1].view(np.float64), r[:, 2])
    return multiset_difference(split(a), split(b))


def _exactly_once(c: Check, dep) -> None:
    route = dep.ref.route if dep.between else lambda *ev: ev
    got = [entry[2] for entry in dep.recorder.log if entry[0] == "batch"]
    got += [(b.key, b.value, b.ts, b.kind) for b in dep.queued]
    got += [route(b.key, b.value, b.ts, b.kind) for b in dep.queued_upstream]
    c.counts["routing_mismatch"] += exactly_once_difference(
        _cat(got), route(*_cat(dep.gen.emitted)))


def check(run, weights: str = "exact") -> Check:
    dep = run.dep
    c = Check(dep, weights)
    c.replay(dep.recorder.log)
    if dep.between:
        c.routing_keys(dep.recorder.log)
    else:
        c.routing_prefix(dep.recorder.log, dep.gen.emitted,
                         int(dep.config["deployment"]["parallelism"]))
    _exactly_once(c, dep)
    if dep.emitted is not None:
        seen: dict[int, float] = {}
        for task, wm, rows in dep.emitted.log:
            want = c.ref.closed(seen.get(task, float("-inf")), wm)
            mine = hash_partition(want[0], dep.emitted.tasks) == task
            c.counts["output_mismatch"] += multiset_difference(
                rows, tuple(col[mine] for col in want))
            seen[task] = wm
    return c
