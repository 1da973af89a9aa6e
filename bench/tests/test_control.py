"""The controls of the comparison at a size a test run holds: the plain
reference put in the system's place with one guarantee broken must come
out as not correct.

* ``per_batch``: a key's weight counts once per batch however often the
  batch writes it (exactly-once state broken);
* ``bfloat16``: weights kept in bfloat16.  Exact up to 256, so it fails
  only where a key's weight passes 256: q11's state starts as an hour of
  its stream leaves it (``history``), which at this size puts a hot key's
  weight near 29,000 (8 bids a second for 3,600 s).
"""
import pytest

from bench.tests.conftest import run_tiny


@pytest.mark.parametrize("weights", ["per_batch", "bfloat16"])
def test_control_is_not_correct(weights):
    res = run_tiny("q11.steady", weights=weights, warm_ticks=4)
    assert not res["correct"]
    assert res["checks"]["state_mismatch"]["value"] > 0
    assert res["checks"]["output_mismatch"]["value"] == 0


def test_per_batch_control_fails_every_cell(workload):
    res = run_tiny(workload, weights="per_batch", warm_ticks=4)
    assert res["checks"]["state_mismatch"]["value"] > 0, res["checks"]


def test_exact_reference_is_correct_on_the_control_traffic():
    res = run_tiny("q11.steady", warm_ticks=4)
    assert res["correct"], res["checks"]
