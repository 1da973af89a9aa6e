"""The reduction from a profiler trace to device numbers, on a trace
built by hand (exact answers) and on a small trace recorded on a TPU v5
lite (``data/trace_small.json``: two probes and two weight sums under
the benchmark's annotations)."""
import json
import pathlib

import numpy as np
import pytest

from bench.trace import LAUNCH, Summary, overlap, union

DATA = pathlib.Path(__file__).with_name("data") / "trace_small.json"


def _planes(early: float = 0.0):
    """Device events ``early`` ns ahead of the host clock."""
    host = [["bench.window", 0, 100], ["bench.tick", 0, 100],
            ["lsm.get_batch", 10, 30], ["lsm.probe", 15, 20],
            ["lsm.segment_sum", 60, 20]]
    launches = [[LAUNCH, t, 0.5] for t in (15.5, 61, 94)]
    ops = [["fusion", 16, 4], ["sorted_probe", 20, 10], ["fusion", 25, 10],
           ["window_agg", 62, 8], ["copy", 95, 10]]
    modules = [["jit_sorted_probe", 16, 20], ["jit_window_agg", 62, 8],
               ["jit_copy", 95, 10]]
    ops, modules = ([[n, s - early, d] for n, s, d in ev]
                    for ev in (ops, modules))
    return [{"name": "/host:CPU", "lines": [
                {"name": "python", "events": host},
                {"name": "main", "events": launches}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": modules},
                {"name": "XLA Ops", "events": ops}]}]


def test_union_and_overlap():
    u = union(np.array([[5, 9], [0, 2], [1, 3], [9, 12]], float))
    assert u.tolist() == [[0, 3], [5, 12]]
    assert overlap(u, np.array([[2, 6], [10, 20]], float)) == 4.0


@pytest.mark.parametrize("early", [0.0, 5.0])
def test_summary_of_a_hand_built_trace(early):
    s = Summary(_planes(early))
    assert s.window_s == pytest.approx(100e-9)
    # busy: [16, 35] + [62, 70] + [95, 100] (clipped to the window)
    assert s.busy_s == pytest.approx(32e-9 if early == 0 else 32.5e-9)
    # device time of the programs launched inside each annotation
    assert s.device_s_in("lsm.probe") == pytest.approx(20e-9)
    assert s.device_s_in("lsm.get_batch") == pytest.approx(20e-9)
    assert s.device_s_in("lsm.segment_sum") == pytest.approx(8e-9)
    assert s.device_s_in("engine.reconfigure") is None
    assert s.launches_in("lsm.get_batch") == 1
    assert s.span_share("lsm.probe", "bench.tick") == pytest.approx(20.0)
    top = dict(s.top_ops())
    assert top["fusion"] == pytest.approx(14e-9)
    # clipped at the window's end (the shifted copy starts 0.5 ns earlier)
    assert top["copy"] == pytest.approx(5e-9 if early == 0 else 5.5e-9)
    gaps = dict(s.idle_gaps(["bench.tick", "lsm.get_batch", "lsm.probe",
                             "lsm.segment_sum"]))
    # idle: [0,16] tick, [35,62] tick (midpoint 48.5), [70,95] tick; a
    # device clock 5 ns early is shifted back by the least shift that puts
    # no program before its launch (4.5 ns here)
    assert list(gaps) == ["bench.tick"]
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_summary_without_a_device_reads_nothing():
    planes = [p for p in _planes() if p["name"].startswith("/host")]
    s = Summary(planes)
    assert s.device_s_in("lsm.probe") is None
    assert s.launches_in("lsm.get_batch") is None


@pytest.mark.parametrize("data", ["hand_built", "recorded"])
def test_summary_with_a_launch_missing_reads_no_device_time(data):
    """A trace whose host launches and device programs do not pair up (the
    profiler dropped one) puts no device time down to a call site."""
    planes = _planes(5.0) if data == "hand_built" else \
        json.loads(DATA.read_text())
    for p in planes:
        for ln in p["lines"]:
            drop = [i for i, e in enumerate(ln["events"]) if e[0] == LAUNCH]
            if drop:
                del ln["events"][drop[0]]
    s = Summary(planes)
    assert not s.aligned
    assert s.device_s_in("lsm.probe") is None
    assert s.device_s_in("lsm.segment_sum") is None
    assert s.idle_gaps(["lsm.probe", "lsm.segment_sum"]) is None
    assert 0 < s.busy_s < s.window_s
    assert s.top_ops()


def test_summary_of_a_recorded_trace():
    s = Summary(json.loads(DATA.read_text()))
    assert 0 < s.busy_s < s.window_s
    probe = s.device_s_in("lsm.probe")
    agg = s.device_s_in("lsm.segment_sum")
    assert probe > 0 and agg > 0
    # a program's time covers its operations and the short gaps between
    assert probe + agg == pytest.approx(s.busy_s, rel=0.05)
    assert s.launches_in("lsm.get_batch") >= 2
    top = dict(s.top_ops())
    assert any(k.startswith("%sorted_probe") for k in top)
    assert any(k.startswith("%window_agg") for k in top)
    gaps = s.idle_gaps(["lsm.get_batch", "lsm.probe", "lsm.segment_sum"])
    assert sum(v for _, v in gaps) == pytest.approx(s.window_s - s.busy_s)
