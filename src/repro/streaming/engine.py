"""Discrete-time streaming executor.

Events are *really processed* (real LSM state, real vectorized operator
compute); only wall-clock is modeled: each task has a per-tick time budget
and each processed chunk charges ``events x cpu_cost + measured state
latency`` against it: capacity comes from the calibrated service-time
model over real executed work, not from the paper's SSD testbed.

Mechanics faithful to Flink/the paper:
  * hash partitioning of keyed streams onto an operator's tasks,
  * bounded inter-op queues -> backpressure (upstream blocks when a
    downstream task queue is full),
  * busyness = fraction of the tick spent processing (DS2's trigger metric),
  * θ / τ read from each task's LSM metrics (Justin's trigger metrics),
  * epoch-barrier snapshots + restore (fault tolerance),
  * reconfiguration with state re-partitioning (scale out/in) and state
    backend resize (scale up/down),
  * straggler mitigation: queue re-balancing for stateless tasks; slowdown
    injection for tests,
  * event-time watermarks for operators that ask for them: a task's is
    the least event time every upstream task has finished, held back by
    its own queue (``_advance``).

Fast-path invariants (the coalesced processing path MUST preserve these —
they are what the golden-trace regression test pins down):

  * **Budget semantics.**  A task keeps processing while its per-tick time
    budget is positive; events left unprocessed stay queued so backlog and
    backpressure build exactly as before.  Coalescing only changes the
    *granularity*: instead of fixed 2048-event chunks, each ``op.process``
    call takes ``budget / cost_per_event`` events sized by a per-task cost
    estimate measured from the previous call (first call after (re)start is
    one chunk, to calibrate).  Overshoot past the budget is bounded by the
    estimate drift, as the chunked path's was bounded by one chunk cost.
  * **Charge model.**  Cost per call is still ``events x cpu_cost_us +
    measured state-latency delta``, scaled by the straggler slowdown.  The
    state-latency delta is read from O(1) scalar metric counters
    (``LSMMetrics.counters()``) — no dict snapshots on the hot path.
  * **Ordering.**  Events are processed in queue order; a partially-taken
    batch's remainder returns to the queue head.  Per-tick topological op
    order and intra-op task order are unchanged.
  * **Backpressure.**  ``_downstream_room`` is evaluated once per op per
    tick (as before) but from incrementally-maintained over-capacity
    counters rather than a scan of every downstream task queue.
  * **State visibility.**  Within one coalesced batch an operator sees its
    own writes exactly as it did within one chunk; pairs that formerly
    matched *across* chunks of the same tick may now fall in one call
    (joins resolve them in the probe direction that stored first).  This
    shifts per-window selectivity by O(chunk/tick_events) but leaves rate,
    busyness, θ and τ statistics — and therefore DS2/Justin decisions —
    unchanged on the golden traces.

Paper-symbol map (what ``collect()`` hands the policies):

=============  ==========================================================
paper          here
=============  ==========================================================
busyness       ``busy_s / task_time_s`` per window — DS2's signal (§2.2)
θ (theta)      ``1 - level_probes/reads``: the fraction of state reads
               served without probing an on-"disk" LSM level (memtable +
               block cache hits + bloom-filtered negatives) — Justin's
               cache-hit-rate signal (§4.2); ``None`` for operators that
               did no reads this window
τ (tau_ms)     ``latency_ms / (reads+writes)``: mean state-access latency
               measured by the LSM store — Justin's latency signal (§4.2)
memory ladder  ``level_mb(level)`` = 158·2^level MB of managed memory per
               task (§5's base grant); ``memory_level=None`` is ⊥, the
               no-managed-memory grant for stateless operators; enacting
               a new level goes through ``reconfigure`` → the state
               backend ``resize`` (scale up/down) with a cold cache — the
               stabilization period §5 describes
C^t            ``reconfigure(new_config)`` applies the controller's
               per-operator ``(parallelism, memory_level)``: parallelism
               changes re-partition state by key hash, level changes
               resize the backend
=============  ==========================================================
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import numpy as np

from repro.obs.spans import span, step
from repro.state.lsm import stable_argsort_keys
from repro.streaming.events import EventBatch, hash_partition
from repro.streaming.graph import Dataflow
from repro.streaming.operators import (WINDOW_BITS, JoinOp, Operator,
                                       SourceOp, WindowAggOp)

BASE_MEM_MB = 158.0                  # default managed memory per slot (§5)


def level_mb(level: int | None, base_mb: float = BASE_MEM_MB) -> float:
    """Justin memory levels: level x doubles the base grant; ⊥ -> 0."""
    return 0.0 if level is None else base_mb * (2 ** level)


def state_partition_keys(op: Operator, state_keys: np.ndarray) -> np.ndarray:
    """Recover the event key a state entry belongs to (for re-partitioning):
    the key above a window id for ``WindowAggOp`` and the tumbling join;
    every other operator (``HotItemsOp``: the window id) is keyed by the
    state key itself."""
    if isinstance(op, WindowAggOp):
        return state_keys >> np.int64(WINDOW_BITS)
    if isinstance(op, JoinOp):
        k = state_keys
        if op.window_s is not None:
            k = k // np.int64(1 << 16)
        return k // np.int64(4)
    return state_keys


def _partition_groups(part: np.ndarray, p: int):
    """Yield per-partition index arrays in one O(n log n) pass instead of p
    boolean-mask scans.  The stable sort preserves the original relative
    order within each partition (so downstream consumers see the exact
    sequences the masked path produced)."""
    order = np.argsort(part, kind="stable")
    bounds = np.searchsorted(part[order], np.arange(p + 1))
    for i in range(p):
        yield order[bounds[i]:bounds[i + 1]]


@dataclass
class TaskRuntime:
    queue: deque = field(default_factory=deque)
    queued_events: int = 0
    state: object = None             # LSMStore | None
    busy_s: float = 0.0
    processed: int = 0
    slowdown: float = 1.0            # straggler injection factor
    cost_per_event: float | None = None   # EWMA of measured s/event (incl.
                                          # slowdown); None until calibrated


@dataclass
class OpWindowStats:
    """Metrics over one observation window (reset on collect)."""
    in_events: int = 0
    out_events: int = 0
    processed: int = 0
    busy_s: float = 0.0
    task_time_s: float = 0.0
    blocked: bool = False
    cache_hits: int = 0
    cache_misses: int = 0
    level_probes: int = 0
    reads: int = 0
    writes: int = 0
    latency_ms: float = 0.0


class StreamEngine:
    def __init__(self, flow: Dataflow, *, tick_s: float = 1.0,
                 chunk_events: int = 2048, queue_cap_events: int = 200_000,
                 base_mem_mb: float = BASE_MEM_MB, seed: int = 0,
                 warm: bool = True):
        self.flow = flow
        self.tick_s = tick_s
        self.chunk = chunk_events
        self.queue_cap = queue_cap_events
        self.base_mem_mb = base_mem_mb
        self.rng = np.random.default_rng(seed)
        self.now = 0.0
        self.topo = flow.topo_order()
        self.tasks: dict[str, list[TaskRuntime]] = {}
        self.stats: dict[str, OpWindowStats] = {}
        self._lsm_marks: dict[tuple[str, int], tuple] = {}
        self._down = {n: flow.downstream(n) for n in self.topo}
        self._over: dict[str, int] = {}   # tasks per op with queue over cap
        self.source_emitted = 0
        self.source_target_rate = 0.0
        # event-time progress, kept only where an event-time operator
        # reads it: for each operator upstream of one (and for each such
        # operator), the lowest timestamp it may still process or emit
        self._low: dict[str, float] = {}
        self._up: dict[str, list[str]] = {}
        for name in self.topo:
            if flow.nodes[name].op.event_time:
                todo = [name]
                while todo:
                    n = todo.pop()
                    if n not in self._up:
                        self._up[n] = flow.upstream(n)
                        todo.extend(self._up[n])
        for name in self.topo:
            self._init_op(name, warm=warm)

    # ------------------------------------------------------------- lifecycle
    def _init_op(self, name: str, *, warm: bool,
                 snapshots: list[dict] | None = None) -> None:
        node = self.flow.nodes[name]
        p = node.parallelism
        tasks = []
        for i in range(p):
            tr = TaskRuntime()
            if node.op.stateful:
                mb = level_mb(node.memory_level, self.base_mem_mb)
                tr.state = node.op.make_state(mb, seed=i)
            tasks.append(tr)
        self.tasks[name] = tasks
        self.stats[name] = OpWindowStats()
        self._over[name] = 0
        if node.op.stateful:
            if snapshots is not None:
                self._load_state(name, snapshots)
            elif warm:
                self._warm(name)
        for i, tr in enumerate(tasks):
            if tr.state is not None:
                self._lsm_marks[(name, i)] = tr.state.metrics.counters()

    def _warm(self, name: str) -> None:
        node = self.flow.nodes[name]
        probe = node.op.make_state(1.0)
        if not hasattr(node.op, "warm_state"):
            return
        # build the full keyspace once, partition onto tasks
        tmp = node.op.make_state(64.0, seed=123)
        node.op.warm_state(tmp, self.rng)
        keys, vals = tmp.items()
        if len(keys) == 0:
            return
        self._install_partitions(name, [{"keys": keys, "vals": vals}])

    def _install_partitions(self, name: str, sources: list[dict]) -> None:
        """Distribute state snapshots onto the op's tasks.

        Equivalent to one global stable sort of the concatenated sources
        by (partition, key), done per source instead, exploiting what
        snapshots guarantee: keys are already sorted.  Per source, one
        stable sort by destination partition keeps each destination slice
        key-sorted; per destination, the per-source slices are sorted runs
        merged by a single stable argsort over their concatenation (ties
        resolve in source order, duplicates across sources included).
        Each task gets its merged partition as one installed run plus a
        cache prewarm over the partition in original arrival order
        (per-source ascending positions, sources in order), drawing from
        the shared rng task by task."""
        with span("engine.install"):
            node = self.flow.nodes[name]
            p = len(self.tasks[name])
            assert p <= (1 << 16)    # partition ids must survive the uint16
            dk = [[] for _ in range(p)]          # key-sorted run fragments
            dw = [[] for _ in range(p)]
            dv = [[] for _ in range(p)]
            ak = [[] for _ in range(p)]      # arrival-order prewarm fragments
            av = [[] for _ in range(p)]
            for s in sources:
                keys = np.asarray(s["keys"], np.int64)
                if not len(keys):
                    continue
                vals = np.asarray(s["vals"], np.int32)
                w = s.get("weights")
                w = np.ones(len(keys), np.int64) if w is None \
                    else np.asarray(w, np.int64)
                with span("engine.partition"):
                    part = hash_partition(
                        state_partition_keys(node.op, keys), p)
                    # uint16 cast => numpy radix-sorts the partition ids
                    order = np.argsort(part.astype(np.uint16), kind="stable")
                    bounds = np.searchsorted(part[order], np.arange(p + 1))
                for i in range(p):
                    # stable sort on partition only => each slice is
                    # already in original arrival order, so the install
                    # fragment doubles as the prewarm fragment (no second
                    # gather)
                    sl = order[bounds[i]:bounds[i + 1]]
                    if not len(sl):
                        continue
                    kk, vv = keys[sl], vals[sl]
                    dk[i].append(kk)
                    dw[i].append(w[sl])
                    dv[i].append(vv)
                    ak[i].append(kk)
                    av[i].append(vv)
            for i in range(p):
                tr = self.tasks[name][i]
                if dk[i]:
                    if len(dk[i]) == 1:
                        mk, mw, mv = dk[i][0], dw[i][0], dv[i][0]
                    else:
                        mk = np.concatenate(dk[i])
                        mw = np.concatenate(dw[i])
                        mv = np.concatenate(dv[i])
                    if len(mk) > 1 and (len(dk[i]) > 1
                                        or np.any(mk[1:] < mk[:-1])):
                        o = stable_argsort_keys(mk)
                        mk, mw, mv = mk[o], mw[o], mv[o]
                    tr.state.install_run(mk, mv, mw)
                    wk = ak[i][0] if len(ak[i]) == 1 else np.concatenate(ak[i])
                    wv = av[i][0] if len(av[i]) == 1 else np.concatenate(av[i])
                    tr.state.prewarm_cache(wk, wv, self.rng)
                tr.state.metrics.reset()

    # ------------------------------------------------------------ snapshots
    def snapshot(self) -> dict:
        """Epoch-barrier snapshot of all operator state + clock."""
        snap = {"now": self.now, "source_emitted": self.source_emitted,
                "ops": {}}
        for name, tasks in self.tasks.items():
            if self.flow.nodes[name].op.stateful:
                snap["ops"][name] = [t.state.snapshot() for t in tasks]
        return snap

    def restore(self, snap: dict) -> None:
        self.now = snap["now"]
        self.source_emitted = snap["source_emitted"]
        for name in self.topo:
            if name in snap["ops"]:
                self._init_op(name, warm=False, snapshots=snap["ops"][name])

    def _load_state(self, name: str, snapshots: list[dict]) -> None:
        sources = [s for s in snapshots if len(s["keys"])]
        if sources:
            self._install_partitions(name, sources)

    # -------------------------------------------------------- reconfiguration
    def reconfigure(self, new_config: dict[str, tuple[int, int | None]]
                    ) -> None:
        """Apply C^t: scale out/in re-partitions state; scale up/down resizes
        the state backend (both incur a cold cache — the stabilization period
        the paper describes)."""
        with span("engine.reconfigure"):
            for name, (p, lvl) in new_config.items():
                node = self.flow.nodes[name]
                p_old, lvl_old = node.parallelism, node.memory_level
                lvl = lvl if node.op.stateful else None
                if p == p_old and lvl == lvl_old:
                    continue
                snaps = None
                if node.op.stateful:
                    snaps = [t.state.snapshot() for t in self.tasks[name]]
                node.parallelism = p
                node.memory_level = lvl
                self._init_op(name, warm=False, snapshots=snaps)

    # ---------------------------------------------------------- fault hooks
    def kill_task(self, name: str, idx: int) -> None:
        """Simulate a task/TM loss: its state and queue are gone."""
        node = self.flow.nodes[name]
        tr = TaskRuntime()
        if node.op.stateful:
            tr.state = node.op.make_state(
                level_mb(node.memory_level, self.base_mem_mb), seed=idx)
            self._lsm_marks[(name, idx)] = tr.state.metrics.counters()
        self.tasks[name][idx] = tr
        self._over[name] = sum(t.queued_events > self.queue_cap
                               for t in self.tasks[name])

    def set_straggler(self, name: str, idx: int, factor: float) -> None:
        self.tasks[name][idx].slowdown = factor

    # ------------------------------------------------------------- execution
    def _queued_delta(self, name: str, tr: TaskRuntime, delta: int) -> None:
        """Adjust a task's queued-event count, maintaining the per-op
        over-capacity counter ``_downstream_room`` reads."""
        if delta == 0:
            return
        was_over = tr.queued_events > self.queue_cap
        tr.queued_events += delta
        if (tr.queued_events > self.queue_cap) != was_over:
            self._over[name] += -1 if was_over else 1

    def _emit(self, name: str, out: EventBatch) -> None:
        if len(out) == 0:
            return
        with span("engine.emit"):
            for d in self._down[name]:
                dn = self.flow.nodes[d]
                if dn.op.stateful:
                    part = hash_partition(out.key, dn.parallelism)
                    for i, sl in enumerate(
                            _partition_groups(part, dn.parallelism)):
                        if len(sl):
                            sub = out.select(sl)
                            t = self.tasks[d][i]
                            t.queue.append(sub)
                            self._queued_delta(d, t, len(sub))
                else:                                   # rebalance round-robin
                    # stable: quicksort's tie order diverges from index order
                    # at >=17 tasks, making the rebalance assignment depend on
                    # sort-algorithm internals instead of task index
                    loads = [t.queued_events for t in self.tasks[d]]
                    order = np.argsort(loads, kind="stable")
                    # same contiguous ranges np.array_split produces, as views
                    q, r = divmod(len(out), dn.parallelism)
                    lo = 0
                    for j, i in enumerate(order):
                        hi = lo + q + (1 if j < r else 0)
                        if hi > lo:
                            sub = out.slice(lo, hi)
                            t = self.tasks[d][i]
                            t.queue.append(sub)
                            self._queued_delta(d, t, len(sub))
                        lo = hi
                self.stats[d].in_events += len(out)

    def _downstream_room(self, name: str) -> bool:
        for d in self._down[name]:
            if self._over[d]:
                return False
        return True

    def _take(self, name: str, tr: TaskRuntime, n: int) -> EventBatch:
        """Pop up to ``n`` events off the head batch of a task queue; a
        partially-consumed batch's remainder returns to the queue head.
        Deliberately does NOT coalesce across queued-batch boundaries:
        the chunked path processed each queued batch's tail fragment as
        its own (cheap) call, and those fragment ticks are part of the
        throughput profile the golden traces pin down."""
        b = tr.queue.popleft()
        if len(b) > n:
            b, rest = b.split(n)
            tr.queue.appendleft(rest)
        self._queued_delta(name, tr, -len(b))
        return b

    def _charge(self, name: str, idx: int) -> float:
        """State-latency delta (s) since the last mark for this task —
        O(1) scalar counter reads, no dict snapshot."""
        tr = self.tasks[name][idx]
        if tr.state is None:
            return 0.0
        mt = tr.state.metrics
        r0, w0, h0, m0, p0, l0 = self._lsm_marks[(name, idx)]
        st = self.stats[name]
        st.reads += mt.reads - r0
        st.writes += mt.writes - w0
        st.cache_hits += mt.cache_hits - h0
        st.cache_misses += mt.cache_misses - m0
        st.level_probes += mt.level_probes - p0
        d_lat = mt.access_latency_total_ms - l0
        st.latency_ms += d_lat
        self._lsm_marks[(name, idx)] = mt.counters()
        return d_lat / 1e3

    def run_tick(self, target_rate: float) -> None:
        with step("engine.tick", round(self.now / self.tick_s)):
            self.source_target_rate = target_rate
            for name in self.topo:
                node = self.flow.nodes[name]
                op = node.op
                st = self.stats[name]
                if isinstance(op, SourceOp):
                    if self._downstream_room(name):
                        n = int(target_rate * self.tick_s)
                        out = op.emit(n, self.now)
                        self.source_emitted += len(out)
                        st.in_events += len(out)
                        st.out_events += len(out)
                        st.processed += len(out)
                        # source busyness: proportional to emitted volume
                        per_task = len(out) * op.cpu_cost_us * 1e-6 \
                            / node.parallelism
                        for tr in self.tasks[name]:
                            tr.busy_s += min(per_task, self.tick_s)
                        self._emit(name, out)
                    else:
                        st.blocked = True
                    st.task_time_s += self.tick_s * node.parallelism
                    if name in self._up:     # next tick's events come later
                        self._low[name] = self.now + self.tick_s
                    continue

                room = self._downstream_room(name)
                for idx, tr in enumerate(self.tasks[name]):
                    budget = self.tick_s
                    while budget > 0 and tr.queue and room:
                        # coalesce queued batches into one vectorized process
                        # call sized by the task's measured per-event cost.
                        # Takes are chunk-quantized and never target more than
                        # a third of the tick, so the tick ends on single-chunk
                        # takes — reproducing the chunked path's last-chunk
                        # budget-overshoot profile (which DS2's capacity
                        # estimate is mildly sensitive to) at a fraction of
                        # the process-call count.
                        if tr.cost_per_event is None:    # calibration take
                            n_take = self.chunk
                        else:
                            plan = int(min(budget, self.tick_s / 3)
                                       / tr.cost_per_event)
                            n_take = max(self.chunk, plan // self.chunk
                                         * self.chunk)
                        batch = self._take(name, tr, n_take)
                        with span("engine.process", op=name, task=idx):
                            out = op.process(tr.state, batch)
                        cost = (len(batch) * op.cpu_cost_us * 1e-6
                                + self._charge(name, idx))
                        cost *= tr.slowdown
                        per = cost / len(batch)
                        tr.cost_per_event = per if tr.cost_per_event is None \
                            else 0.5 * tr.cost_per_event + 0.5 * per
                        budget -= cost
                        tr.busy_s += cost
                        tr.processed += len(batch)
                        st.processed += len(batch)
                        st.out_events += len(out)
                        self._emit(name, out)
                    st.busy_s += min(self.tick_s, self.tick_s - budget) \
                        if budget < self.tick_s else self.tick_s - budget
                    st.task_time_s += self.tick_s
                    if not room:
                        st.blocked = True
                # straggler mitigation: re-balance stateless task queues
                if not op.stateful and node.parallelism > 1:
                    self._rebalance(name)
                if name in self._up:
                    self._advance(name)
            self.now += self.tick_s

    def _advance(self, name: str) -> None:
        """Event-time progress after ``name``'s turn in a tick (Flink's
        watermark rule): what reaches a task comes from every upstream
        task, so its input watermark is the least of the upstream
        operators' low marks; its own watermark also waits for the
        events still in its queue.  An event-time operator's tasks get
        theirs through ``on_watermark``; the operator's low mark is the
        least over its tasks."""
        op = self.flow.nodes[name].op
        w_in = min((self._low[u] for u in self._up[name]), default=np.inf)
        low = w_in
        for tr in self.tasks[name]:
            wm = min([w_in] + [float(b.ts.min()) for b in tr.queue])
            low = min(low, wm)
            if op.event_time:
                out = op.on_watermark(tr.state, wm)
                self.stats[name].out_events += len(out)
                self._emit(name, out)
        self._low[name] = low

    def _rebalance(self, name: str) -> None:
        tasks = self.tasks[name]
        loads = np.array([t.queued_events for t in tasks])
        if loads.max() > 4 * max(1, np.median(loads)) + self.chunk:
            src = tasks[int(loads.argmax())]
            dst = tasks[int(loads.argmin())]
            move = len(src.queue) // 2
            for _ in range(move):
                b = src.queue.pop()
                self._queued_delta(name, src, -len(b))
                dst.queue.append(b)
                self._queued_delta(name, dst, len(b))

    def run(self, seconds: float, target_rate: float) -> None:
        for _ in range(int(round(seconds / self.tick_s))):
            self.run_tick(target_rate)

    def run_paused(self, seconds: float, target_rate: float) -> None:
        """Reconfiguration downtime: the job is stopped, the world is not.
        Sources keep producing (they model external arrival — a Kafka
        topic does not pause for a savepoint) until backpressure blocks
        them, but NO operator processes, so arrivals accrue as queued
        backlog the resumed configuration must drain — the catch-up the
        SLO metrics measure.  Task time accrues for every operator so a
        caller collecting over the pause sees diluted busyness; on the
        controller path these stats are discarded with the stabilization
        window, and the cost surfaces through the backlog alone."""
        for _ in range(int(round(seconds / self.tick_s))):
            for name in self.topo:
                node = self.flow.nodes[name]
                st = self.stats[name]
                st.task_time_s += self.tick_s * node.parallelism
                if isinstance(node.op, SourceOp):
                    if self._downstream_room(name):
                        out = node.op.emit(int(target_rate * self.tick_s),
                                           self.now)
                        self.source_emitted += len(out)
                        st.in_events += len(out)
                        st.out_events += len(out)
                        st.processed += len(out)
                        self._emit(name, out)
                    else:
                        st.blocked = True
            self.now += self.tick_s

    # --------------------------------------------------------------- metrics
    def collect(self, reset: bool = True) -> dict[str, dict]:
        out = {}
        for name in self.topo:
            node = self.flow.nodes[name]
            st = self.stats[name]
            dur = max(st.task_time_s / max(node.parallelism, 1), 1e-9)
            sops = st.reads + st.writes
            # θ: effective in-memory hit rate — the fraction of reads that
            # avoided the slow tier (memtable + block cache + bloom-filtered
            # negatives; paper §4: "a significant fraction of accesses ...
            # used the disk").  Block-cache-only rate is kept in the LSM
            # metrics for diagnostics.
            theta = max(0.0, 1.0 - st.level_probes / st.reads) \
                if st.reads else None
            out[name] = {
                "stateful": node.op.stateful,
                "parallelism": node.parallelism,
                "memory_level": node.memory_level,
                "rate_in": st.in_events / dur,
                "rate_out": st.out_events / dur,
                "rate_processed": st.processed / dur,
                "busyness": st.busy_s / max(st.task_time_s, 1e-9),
                "busy_s": st.busy_s,
                "processed": st.processed,
                "selectivity": st.out_events / max(st.in_events, 1),
                "theta": theta,
                "tau_ms": (st.latency_ms / sops) if sops else None,
                "blocked": st.blocked,
                "backlog": sum(t.queued_events for t in self.tasks[name]),
            }
            if reset:
                self.stats[name] = OpWindowStats()
        return out
