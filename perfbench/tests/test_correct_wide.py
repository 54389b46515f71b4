"""``correct`` of the wide cell at a size a test run can hold (wide_cell.py:
``epsilon_train`` shrunk, two group chunks, the reference in feature
blocks): true for a sound run, false for the int4 control and for each
fault of faults.py.  The limits are small_limits_wide.json, from this
size's own readings.

    python -m pytest perfbench/tests/test_correct_wide.py -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import faults
import run
import wide_cell


@pytest.fixture(scope="module")
def sound():
    with pytest.MonkeyPatch.context() as mp:
        return wide_cell.drive(mp)


def test_a_sound_run_is_correct(sound):
    line, info, _ = sound
    assert line["correct"], info["verdict"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert info["gauges"]["grower.hist_group_chunks"] == 2
    assert set(line["compared"]) == set(
        wide_cell.small_cell()["limits"]["limits"])


def test_the_control_is_not_correct(sound):
    _, info, _ = sound
    verdict = run.judge(info["numbers"]["control"],
                        wide_cell.small_cell()["limits"])
    assert not all(ok for *_, ok in verdict), verdict
    assert info["control_correct"] is False


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_fault_is_not_correct(fault, monkeypatch):
    with faults.FAULTS[fault]():
        line, info, _ = wide_cell.drive(monkeypatch)
    assert not line["correct"], info["verdict"]
