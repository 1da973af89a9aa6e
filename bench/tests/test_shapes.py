"""``bench/shapes.py`` records the padded shapes that the store launches:
at a tiny size, the shapes it records with its numpy stand-ins are the
shapes the real kernels (interpreted) are launched with, and the list it
gives for the traffic file holds them all."""
import repro.kernels.sorted_probe.kernel as probe_kernel
import repro.kernels.window_agg.kernel as agg_kernel
from repro.kernels.device import bucket
from repro.state import lsm

from bench import shapes
from bench.tests.conftest import tiny_cell


def _launched_by_real_kernels(monkeypatch, cell, seed, steps):
    seen = {"sorted_probe": set(), "window_agg": set()}
    probe, agg = probe_kernel.sorted_probe, agg_kernel.window_agg

    def probe_seen(t_hi, t_lo, q_hi, q_lo, *, interpret=False):
        seen["sorted_probe"].add((len(t_hi), len(q_hi)))
        return probe(t_hi, t_lo, q_hi, q_lo, interpret=interpret)

    def agg_seen(seg_ids, values, n_segments, *, interpret=False):
        seen["window_agg"].add((len(seg_ids), n_segments, values.shape[0]))
        return agg(seg_ids, values, n_segments, interpret=interpret)

    monkeypatch.setattr(probe_kernel, "sorted_probe", probe_seen)
    monkeypatch.setattr(agg_kernel, "window_agg", agg_seen)
    from bench import harness
    run = harness.Run(cell, seed, 0.0, False, "interpret")
    cell.driver.setup(run)
    for _ in range(steps):
        cell.driver.step(run)
    monkeypatch.setattr(probe_kernel, "sorted_probe", probe)
    monkeypatch.setattr(agg_kernel, "window_agg", agg)
    return seen


def test_recorded_shapes_are_the_launched_shapes(workload, monkeypatch):
    monkeypatch.setattr(lsm, "DEFAULT_KERNEL_IMPL", "interpret")
    want = _launched_by_real_kernels(monkeypatch, tiny_cell(workload), 7, 3)
    got = shapes.record(tiny_cell(workload), [7], 3)
    assert got == want
    assert got["sorted_probe"]
    for t, q in got["sorted_probe"]:
        assert t == bucket(t, probe_kernel.TABLE_TILE)
        assert q == bucket(q, probe_kernel.QUERY_BLOCK)
    group = shapes.closure(got)
    for kernel, seen in got.items():
        assert seen <= {tuple(s) for s in group[kernel]}
