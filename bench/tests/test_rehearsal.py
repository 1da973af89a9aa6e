"""CPU rehearsal of every driver kind at a tiny size: the comparison
passes on the system as it is, and fails when the timed path is broken
underneath (a found bit flipped, a weight altered, a write that leaves
the state unchanged, half of a read batch left out).  The cells run on
one chip, so there is no exchange between chips to leave out."""
import numpy as np
import pytest

from repro.state.lsm import LSMStore
from bench.tests.conftest import run_tiny


def test_tiny_cell_is_correct(workload):
    res = run_tiny(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def _flip_one_found_bit(monkeypatch):
    orig, done = LSMStore.get_batch, []

    def get_batch(self, keys, uhint=None):
        vals, found = orig(self, keys, uhint)
        # one present key with a written payload read as absent, as a
        # probe that missed it would answer: not found, zero payload
        hit = np.flatnonzero(found & vals.any(axis=1))
        if not done and len(hit):
            vals, found = vals.copy(), found.copy()
            same = keys == keys[hit[0]]
            found[same], vals[same] = False, 0
            done.append(1)
        return vals, found
    monkeypatch.setattr(LSMStore, "get_batch", get_batch)


def _alter_one_weight(monkeypatch):
    orig, done = LSMStore._segment_sum, []

    def segment_sum(self, sorted_w, starts, first_mask):
        out = orig(self, sorted_w, starts, first_mask)
        if not done and len(out):
            out = out.copy()
            out[0] += 1
            done.append(1)
        return out
    monkeypatch.setattr(LSMStore, "_segment_sum", segment_sum)


def _leave_state_unchanged(monkeypatch):
    def put_batch(self, keys, vals):
        return self._delta_of(keys, vals)
    monkeypatch.setattr(LSMStore, "put_batch", put_batch)


def _read_half_the_batch(monkeypatch):
    orig = LSMStore.get_batch

    def get_batch(self, keys, uhint=None):
        half = len(keys) // 2
        vals = np.zeros((len(keys), self.value_words), np.int32)
        found = np.zeros(len(keys), bool)
        if half:
            vals[:half], found[:half] = orig(self, keys[:half])
        return vals, found
    monkeypatch.setattr(LSMStore, "get_batch", get_batch)


FAULTS = {"found_bit": _flip_one_found_bit, "weight": _alter_one_weight,
          "state_unchanged": _leave_state_unchanged,
          "half_batch": _read_half_the_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run_tiny(workload)
    assert not res["correct"], (fault, res["checks"])
    assert res["failed"] > 0
