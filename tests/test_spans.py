"""Profiler-clock spans and in-program counters (``repro.obs.spans``).

* On a CPU profiler trace of the store (Pallas kernels in interpret
  mode) and of a small engine, every span in ``SPANS`` appears, and the
  kernel's spans nest inside the store's.
* The counters equal the bytes and compares computed from the ``bucket``
  ladder, whether spans are on or off; a table already on the device
  sends only the queries.
* Off, ``span`` hands out one shared null context, and the numpy store
  path never imports ``jax``.
* The golden episodes decide byte-identically with spans on.
"""
import ast
import glob
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.kernels.device import bucket
from repro.kernels.sorted_probe.kernel import QUERY_BLOCK, TABLE_TILE
from repro.kernels.sorted_probe.ops import probe, upload
from repro.kernels.window_agg.kernel import EVENT_TILE, SEG_BLOCK
from repro.kernels.window_agg.ops import aggregate
from repro.obs import spans
from repro.state.lsm import LSMStore

REPO = pathlib.Path(__file__).resolve().parent.parent


def _store_work():
    """Puts in batches small enough to consolidate the delta runs, enough
    of them to flush twice (the second flush compacts), then reads that
    miss the cache and reach the levels, a snapshot, an installed run
    and a cache prewarm."""
    rng = np.random.default_rng(5)
    store = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    keys = rng.permutation(100_000)[:400].astype(np.int64)
    for i in range(0, 300, 10):
        k = keys[i:i + 10]
        store.put_batch(k, np.ones((len(k), 2), np.int32))
    assert store.metrics.flushes >= 2 and store.metrics.compactions >= 1
    q = np.sort(np.r_[keys[:20], keys[300:350]])
    store.get_batch(q)
    snap = store.snapshot()
    fresh = LSMStore(0.5, value_words=2, kernel_impl="interpret")
    fresh.install_run(snap["keys"], snap["vals"], snap["weights"])
    fresh.prewarm_cache(snap["keys"], snap["vals"])


def _engine_work():
    """A keyed-state operator ticked, rescaled and ticked again; then q5
    until its first windows close."""
    from repro.data.nexmark import QUERIES, BidGen
    from repro.streaming.engine import StreamEngine
    from repro.streaming.graph import Dataflow
    from repro.streaming.operators import KeyedStateOp, SinkOp, SourceOp
    f = Dataflow("t")
    f.chain(SourceOp("source", BidGen(seed=1)),
            KeyedStateOp("agg", "update", keyspace=1_000, prepopulate=False),
            SinkOp("sink"))
    f.nodes["source"].op.users = 1_000
    eng = StreamEngine(f, seed=0)
    eng.run(3, 5_000)
    eng.reconfigure({"agg": (3, 1)})
    eng.run(2, 5_000)
    StreamEngine(QUERIES["q5"](), seed=0).run(3, 2_000)


def _host_events(log_dir: str) -> list[tuple]:
    """(name, start_ns, end_ns, arguments, line) of every span on the
    host."""
    import jax
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
             {k: v for k, v in e.stats}, (pl.name, ln.name))
            for pl in data.planes if pl.name.startswith("/host:")
            for ln in pl.lines for e in ln.events if e.name in spans.SPANS]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Host events of one profiler trace of both workloads with spans on,
    every padded shape new to the process, and the counters' change."""
    import jax
    log_dir = str(tmp_path_factory.mktemp("spans"))
    seen, spans._shapes_seen = spans._shapes_seen, set()
    before = spans.counts.copy()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # spans and launches, no calls
    spans.enable(True)
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            _store_work()
            _engine_work()
        finally:
            jax.profiler.stop_trace()
    finally:
        spans.enable(False)
        spans._shapes_seen = seen | spans._shapes_seen
    return _host_events(log_dir), spans.counts - before


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(inner, outers) -> bool:
    return any(o[4] == inner[4] and o[1] <= inner[1] and inner[2] <= o[2]
               for o in outers)


def test_every_span_appears_in_a_trace_of_the_store_and_engine(traced):
    events, _ = traced
    names = {e[0] for e in events}
    assert set(spans.SPANS) <= names, set(spans.SPANS) - names


def test_kernel_spans_nest_inside_the_store_spans(traced):
    events, _ = traced
    waits = _named(events, "sorted_probe.wait")
    probes = _named(events, "lsm.probe")
    gets = _named(events, "lsm.get_batch")
    assert waits and probes and gets
    assert all(_inside(w, probes) for w in waits)
    assert all(_inside(p, gets) for p in probes)
    for tier in ("lsm.read.memtable", "lsm.read.cache", "lsm.read.levels"):
        assert all(_inside(t, gets) for t in _named(events, tier)), tier
    for part in ("prepare", "launch", "wait"):
        sums = _named(events, f"window_agg.{part}")
        assert sums and all(_inside(s, _named(events, "lsm.segment_sum"))
                            for s in sums), part
    # a compile lands on its call site: inside the launch it delayed
    firsts = _named(events, "kernel.first_call")
    launches = _named(events, "sorted_probe.launch") \
        + _named(events, "window_agg.launch")
    assert firsts and all(_inside(f, launches) for f in firsts)
    assert {f[3]["kernel"] for f in firsts} == {"sorted_probe",
                                                "window_agg"}
    shapes = [f[3]["shape"] for f in firsts]
    assert len(shapes) == len(set(shapes))


def test_engine_spans_carry_the_tick_and_the_task(traced):
    events, _ = traced
    ticks = _named(events, "engine.tick")
    assert [t[3]["step_num"] for t in ticks] == [0, 1, 2, 3, 4,  # agg
                                                 0, 1, 2]        # q5
    procs = _named(events, "engine.process")
    assert procs and all(_inside(p, ticks) for p in procs)
    assert {(p[3]["op"], p[3]["task"]) for p in procs} >= {
        ("agg", 0), ("agg", 1), ("agg", 2)}
    installs = _named(events, "engine.install")
    assert installs and all(_inside(i, _named(events, "engine.reconfigure"))
                            for i in installs)
    assert all(_inside(p, installs)
               for p in _named(events, "engine.partition"))


def test_traced_counters_count_every_kernel_call(traced):
    events, counted = traced
    for kernel in ("sorted_probe", "window_agg"):
        assert counted[f"{kernel}.calls"] == len(
            _named(events, f"{kernel}.launch")), kernel
        assert counted[f"{kernel}.h2d_bytes"] > 0, kernel
    # every probe call either uploads its table or finds it resident
    assert counted["sorted_probe.table_uploads"] \
        + counted["sorted_probe.table_reuses"] == counted["sorted_probe.calls"]


@pytest.mark.parametrize("on", [False, True])
def test_counters_equal_the_bucketed_bytes_and_cells(on):
    rng = np.random.default_rng(2)
    t, n = 9_000, 300
    table = np.sort(rng.choice(1 << 40, t, replace=False)).astype(np.int64)
    queries = rng.integers(0, 1 << 40, n).astype(np.int64)
    e, s, v = 1_000, 700, 1
    seg = rng.integers(0, s, e).astype(np.int32)
    vals = rng.integers(0, 5, (e, v)).astype(np.float32)
    spans.enable(on)
    try:
        before = spans.counts.copy()
        resident = upload(table)
        probe(resident, queries, impl="interpret")
        aggregate(seg, vals, s, impl="interpret")
        got = spans.counts - before
        before = spans.counts.copy()
        probe(resident, queries, impl="interpret")
        again = spans.counts - before
    finally:
        spans.enable(False)
    tp, qp = bucket(t, TABLE_TILE), bucket(n, QUERY_BLOCK)
    eb, sb = bucket(e, EVENT_TILE), bucket(s, SEG_BLOCK)
    assert dict(got) == {
        "sorted_probe.calls": 1,
        "sorted_probe.table_uploads": 1,
        "sorted_probe.h2d_bytes": 4 * 2 * (tp + qp),
        "window_agg.calls": 1,
        "window_agg.h2d_bytes": 4 * eb + 4 * v * eb,
        "window_agg.remapped": 1,           # random ids: sorted first
    }
    # the table stayed on the device: the second call sends the queries
    assert dict(again) == {
        "sorted_probe.calls": 1,
        "sorted_probe.table_reuses": 1,
        "sorted_probe.h2d_bytes": 4 * 2 * qp,
    }


def test_off_spans_are_one_shared_null_context():
    assert not spans._on
    null = spans.span("lsm.probe")
    assert spans.span("engine.process", op="agg", task=0) is null
    assert spans.step("engine.tick", 3) is null
    spans.first_call("test_kernel", (1, 2))
    assert spans.first_call("test_kernel", (1, 2)) is null
    with null:
        pass
    spans.enable(True)
    try:
        assert spans.span("lsm.probe") is not null
    finally:
        spans.enable(False)
    assert spans.span("lsm.probe") is null


def test_numpy_store_path_never_imports_jax():
    code = (
        "import sys\n"
        "import numpy as np\n"
        "from repro.data.nexmark import BidGen\n"
        "from repro.streaming.engine import StreamEngine\n"
        "from repro.streaming.graph import Dataflow\n"
        "from repro.streaming.operators import KeyedStateOp, SinkOp, "
        "SourceOp\n"
        "f = Dataflow('t')\n"
        "f.chain(SourceOp('source', BidGen(seed=1)), KeyedStateOp('agg', "
        "'update', keyspace=1_000, prepopulate=False), SinkOp('sink'))\n"
        "eng = StreamEngine(f, seed=0)\n"
        "eng.run(3, 5_000)\n"
        "eng.reconfigure({'agg': (2, 1)})\n"
        "eng.run(1, 5_000)\n"
        "assert eng.tasks['agg'][0].state.metrics.reads\n"
        "loaded = sorted(m for m in sys.modules if m.split('.')[0] == 'jax')\n"
        "assert not loaded, loaded\n")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_store_and_kernels_load_no_policy_layer():
    code = (
        "import sys\n"
        "import repro.kernels.sorted_probe.ops\n"
        "import repro.kernels.window_agg.ops\n"
        "import repro.state.lsm\n"
        "import repro.streaming.engine\n"
        "loaded = sorted(m for m in sys.modules if m.startswith("
        "('repro.core', 'repro.obs.provenance')))\n"
        "assert not loaded, loaded\n"
        "from repro.obs import REASONS\n"
        "assert 'repro.obs.provenance' in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def _opened_names() -> set[str]:
    """Every name the program's sources open through ``span``, ``step``
    or ``first_call`` (which opens ``kernel.first_call``)."""
    names = set()
    for path in (REPO / "src" / "repro").rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func.id if isinstance(node.func, ast.Name) else None
            if fn in ("span", "step") and node.args \
                    and isinstance(node.args[0], ast.Constant):
                names.add(node.args[0].value)
            elif fn == "first_call":
                names.add("kernel.first_call")
    return names


def test_spans_lists_every_name_the_program_opens():
    assert _opened_names() == set(spans.SPANS)


@pytest.mark.parametrize("key", ["q8_justin", "q11_justin", "q11_ds2",
                                 "q8_ds2"])
def test_golden_decisions_with_spans_on(key):
    from test_golden_trace import assert_matches_golden
    spans.enable(True)
    try:
        assert_matches_golden(key)
    finally:
        spans.enable(False)
