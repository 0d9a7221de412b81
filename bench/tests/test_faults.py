"""A run drives the program through the harness and decides ``correct``
by the comparison with the reference: a sound program passes, and each
fault a cell can have, planted under the timed path, makes it fail."""

import pytest

import faults
import registry
import tiny

CELLS = [w["name"] for w in registry.benchmark()["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(cell):
    result, checks = tiny.run(cell)
    assert result["correct"], checks
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {m["name"] for m in registry.end_to_end_for(cell)}


@pytest.mark.parametrize("fault", [f for f in faults.FAULTS if f != "no_exchange"])
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault):
    with faults.planted(fault):
        result, checks = tiny.run(cell)
    assert not result["correct"], checks
