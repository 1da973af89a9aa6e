"""Builds one deployment through the system's normal entry points.

The query's dataflow comes from the system (``repro.data.nexmark``); the
benchmark replaces its source's generator with ``bench.gen.EventGen``,
sets the stateful operator's configuration, and installs the initial
state through ``StreamEngine.restore``, the engine's own install path.
State and events are the benchmark's data, made from the seed, so the
plain reference starts from the same data without taking anything the
system made.  ``Recorder`` keeps what reaches the operator and what it
emits, for the check.
"""
from __future__ import annotations

from bench.gen import EventGen, seed_rng


class Recorder:
    """Wraps the stateful operator's ``process``: every call's task,
    input and output, in the order the engine made them.  Holds
    references only (batches are immutable)."""

    def __init__(self, engine, op_name: str):
        self.engine, self.op_name = engine, op_name
        op = engine.flow.nodes[op_name].op
        self._process = op.process
        op.process = self._record
        self.log: list[tuple] = []        # ("batch", task, in, out) | marks
        self._task_of: dict[int, int] = {}
        self.events = 0

    def _task(self, state) -> int:
        i = self._task_of.get(id(state))
        if i is None:
            self._task_of = {id(t.state): j for j, t in
                             enumerate(self.engine.tasks[self.op_name])}
            i = self._task_of[id(state)]
        return i

    def _record(self, state, batch):
        out = self._process(state, batch)
        self.events += len(batch)
        self.log.append(("batch", self._task(state),
                         (batch.key, batch.value, batch.ts, batch.kind),
                         (out.key, out.value, out.ts, out.kind)))
        return out

    def mark(self, what: str, payload) -> None:
        self.log.append((what, payload))


class Deployment:
    """The engine of one cell with its generator, recorder and data."""

    def __init__(self, config: dict, traffic: dict, seed: int, reference):
        from repro.data.nexmark import QUERIES
        from repro.streaming.engine import StreamEngine
        from repro.streaming.events import EventBatch
        dep = config["deployment"]
        self.config, self.traffic, self.seed = config, traffic, seed
        self.op_name = dep["operator"]
        self.ref = reference
        flow = QUERIES[dep["query"]]()
        node = flow.nodes[self.op_name]
        for key, want in config.get("operator_params", {}).items():
            got = getattr(node.op, key)
            if got != want:
                raise ValueError(f"{self.op_name}.{key} is {got!r} in the "
                                 f"system, {want!r} in the configuration")
        node.parallelism = int(dep["parallelism"])
        node.memory_level = int(dep["memory_level"])
        self.gen = EventGen(config["stream"], config["keyspace"],
                            config["payload_words"], seed)
        gen = self.gen
        src = flow.nodes[flow.sources()[0]].op
        src.generator = lambda n, now: EventBatch(*gen(n, now))
        self.source_op = src
        self.engine = StreamEngine(flow, tick_s=float(traffic["tick_s"]),
                                   seed=seed % (1 << 64), warm=False)
        hist = config.get("history")
        if hist and float(hist["seconds"]) != float(traffic["start_s"]):
            raise ValueError("the traffic must start where the state's "
                             "history ends (start_s == history.seconds)")
        self.initial = self.ref.initial_state(config, seed_rng(seed, 2))
        keys, weights, vals = self.initial
        self.engine.restore({
            "now": float(traffic["start_s"]), "source_emitted": 0,
            "ops": {self.op_name: [{"keys": keys, "vals": vals,
                                    "weights": weights}]}})
        self.recorder = Recorder(self.engine, self.op_name)
        self.rate = float(traffic["rate_events_per_s"])

    def tick(self) -> None:
        self.engine.run_tick(self.rate)

    def task_snapshots(self) -> list[dict]:
        """Every task's epoch snapshot, through the store's own API."""
        return [t.state.snapshot() for t in self.engine.tasks[self.op_name]]

    def free(self) -> None:
        """Drop the system's state; the recorded log stays."""
        self.engine = None
        self.recorder.engine = None
