"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read into plain data: planes, each with lines, each with
events ``[name, start_ns, duration_ns]`` on one clock.  Device planes are
named ``/device:TPU:<n>``; their ``XLA Ops`` line holds the operations
that ran (asynchronous copies appear there as their start and done ops),
their ``XLA Modules`` line one event per program launched.
Host planes hold the annotations the benchmark opened (``bench.window``
around the measured window, and the layer sites of ``bench.hooks``).

From that: the busy union of device operations inside the window and
its idle share, the device time of the programs launched inside the
intervals of an annotation, the launches there, the operations that took
most time, and the idle gaps, each put down to the innermost annotation
open at its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"
LAUNCH = "PJRT_LoadedExecutable_Execute"    # host event: one program launch


def read_profile(log_dir: str) -> list[dict]:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain data."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no profile under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    return [{"name": pl.name,
             "lines": [{"name": ln.name,
                        "events": [[e.name, e.start_ns, e.duration_ns]
                                   for e in ln.events]}
                       for ln in pl.lines]}
            for pl in data.planes]


def union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted union of ``[start, end]`` rows."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > ends[:-1]]
    starts = iv[new, 0]
    stop = np.r_[np.flatnonzero(new)[1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[stop]], axis=1)


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint sorted unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def op_name(text: str) -> str:
    """The HLO text a trace names an operation by, without layouts and
    backend attributes: ``%sorted_probe.1 = (s32[256,128], s32[256,128])
    custom-call(s32[4096,128] %bitcast.2, ...)``."""
    text = re.sub(r"\{[^{}]*\}", "", text)
    return text.split(", custom_call_target", 1)[0]


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


class Summary:
    """The device clock of a trace can read off the host's by a
    millisecond or more (on a TPU v5 lite: device events ~1 ms before the
    host launch that caused them).  So device work is put down to host
    annotations through launches, not through timestamps: the k-th
    ``PJRT_LoadedExecutable_Execute`` on the host launched the k-th
    ``XLA Modules`` event of a device.  The same pairs give the least
    shift that puts no program before its launch, which aligns the
    device's busy intervals for the idle gaps.  Where the counts disagree
    (the profiler dropped events), no program can be paired with its
    launch: ``aligned`` is False, and what needs the pairing
    (``device_s_in``, ``idle_gaps``) reads None."""

    def __init__(self, planes: list[dict]):
        self.devices = [p for p in planes
                        if p["name"].startswith(DEVICE_PREFIX)]
        spans = defaultdict(list)
        for p in planes:
            if not p["name"].startswith(HOST_PREFIX):
                continue
            for ln in p["lines"]:
                for name, start, dur in ln["events"]:
                    spans[name].append((start, start + dur))
        self.spans = {k: np.array(v, np.float64) for k, v in spans.items()}
        win = self.spans.get(WINDOW)
        if win is None or not len(win):
            raise ValueError(f"trace has no {WINDOW!r} annotation")
        self.t0, self.t1 = float(win[:, 0].min()), float(win[:, 1].max())
        launch = self.spans.get(LAUNCH, np.empty((0, 2)))
        launch = launch[np.argsort(launch[:, 0], kind="stable"), 0]
        self.ops, self.busy, self.modules = [], [], []
        self.aligned = True
        for dev in self.devices:
            lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
            mods = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
            starts = np.array([e[1] for e in mods], np.float64)
            shift, at = 0.0, starts
            if len(mods) == len(launch) and len(mods):
                shift = max(0.0, float((launch - starts).max()))
                at = launch
            else:
                self.aligned = False
            self.modules.append([(n, a, d) for (n, _, d), a in zip(mods, at)
                                 if self.t0 <= a < self.t1])
            ops = [(n, s + shift, s + shift + d)
                   for n, s, d in lines.get(OPS_LINE, [])
                   if s + shift + d > self.t0 and s + shift < self.t1]
            self.ops.append(ops)
            iv = np.array([(s, e) for _, s, e in ops], np.float64)
            self.busy.append(union(clip(iv.reshape(-1, 2), self.t0,
                                        self.t1)))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices traced."""
        if not self.busy:
            return 0.0
        return float(np.mean([(b[:, 1] - b[:, 0]).sum() for b in self.busy])
                     ) * 1e-9

    def idle_share(self) -> float | None:
        """Percentage of the window in which no operation ran on the
        device; None where no device was traced."""
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def intervals(self, name: str) -> np.ndarray | None:
        iv = self.spans.get(name)
        if iv is None:
            return None
        return union(clip(iv, self.t0, self.t1))

    @staticmethod
    def _inside(times: np.ndarray, iv: np.ndarray) -> np.ndarray:
        k = np.searchsorted(iv[:, 0], times, side="right") - 1
        ok = k >= 0
        ok[ok] = times[ok] < iv[k[ok], 1]
        return ok

    def device_s_in(self, name: str) -> float | None:
        """Device seconds of the programs launched inside the intervals
        of annotation ``name`` (summed over devices); None where the
        annotation never opened, no device was traced, or programs and
        launches could not be paired."""
        iv = self.intervals(name)
        if not self.devices or not self.aligned or iv is None \
                or not len(iv):
            return None
        total = 0.0
        for mods in self.modules:
            at = np.array([a for _, a, _ in mods], np.float64)
            dur = np.array([d for _, _, d in mods], np.float64)
            total += float(dur[self._inside(at, iv)].sum())
        return total * 1e-9

    def span_share(self, name: str, within: str) -> float | None:
        """Percentage of the time of annotation ``within`` that
        annotation ``name`` was open."""
        inner, outer = self.intervals(name), self.intervals(within)
        if inner is None or outer is None or not len(outer):
            return None
        return 100.0 * overlap(inner, outer) / float(
            (outer[:, 1] - outer[:, 0]).sum())

    def launches_in(self, name: str) -> int | None:
        """Programs the host launched inside the intervals of ``name``."""
        iv, launch = self.intervals(name), self.spans.get(LAUNCH)
        if not self.devices or iv is None or not len(iv) or launch is None:
            return None
        return int(np.count_nonzero(self._inside(launch[:, 0], iv)))

    def top_ops(self, n: int = 10) -> list[list]:
        tot = defaultdict(float)
        for ops in self.ops:
            for name, s, e in ops:
                tot[op_name(name)] += (min(e, self.t1) - max(s, self.t0)) * 1e-9
        return [[k, float(v)] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, names, n: int = 10) -> list[list] | None:
        """Idle seconds of the first device, by the innermost of ``names``
        open at each gap's midpoint (``bench.window`` where none is); None
        where the device's clock could not be aligned with the host's."""
        if not self.busy or not self.aligned:
            return None
        b = self.busy[0]
        edges = np.r_[self.t0, b.ravel(), self.t1].reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        mids = gaps.mean(axis=1)
        best = np.full(len(gaps), np.inf)
        owner = np.full(len(gaps), WINDOW, dtype=object)
        for name in names:
            iv = self.spans.get(name)
            if iv is None:
                continue
            iv = iv[np.argsort(iv[:, 0], kind="stable")]
            k = np.searchsorted(iv[:, 0], mids, side="right") - 1
            ok = k >= 0
            kk = k[ok]
            inside = mids[ok] < iv[kk, 1]
            length = iv[kk, 1] - iv[kk, 0]
            idx = np.flatnonzero(ok)[inside]
            shorter = length[inside] < best[idx]
            best[idx[shorter]] = length[inside][shorter]
            owner[idx[shorter]] = name
        tot = defaultdict(float)
        for name, (lo, hi) in zip(owner, gaps):
            tot[name] += (hi - lo) * 1e-9
        return [[k, float(v)] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
