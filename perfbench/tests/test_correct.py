"""``correct`` at a size a test run can hold (CPU, the kernels through the
program's interpret seam): true for a sound run, false for the control
(the reference's own sums in int4 in the program's place) and for each
fault a training cell can have, planted underneath a whole run of the
harness.  The limits are small_limits.json, set from this size's own
readings as the cell's are from the chip's (PERF.md, section 2).

    python -m pytest perfbench/tests -q        (about seven minutes)
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import faults
import run

SEED = 2147484001          # above 2**31 - 1, as the driver's can be


def small_cell():
    loaded = run.load_cell("criteo_train")
    loaded["config"].update(rows=40000, table_seed=SEED)
    loaded["config"]["params"].update(num_leaves=31)
    loaded["traffic"]["dispatch_chunk"] = 2
    with open(os.path.join(HERE, "small_limits.json")) as f:
        loaded["limits"] = json.load(f)
    return loaded


def drive(seed=SEED):
    import jax
    return run.run_cell(small_cell(), seed, 0.5, False, jax.devices()[:1],
                        interpret=True)


@pytest.fixture(scope="module")
def sound():
    return drive()


def test_a_sound_run_is_correct(sound):
    line, info = sound
    assert line["correct"], info["verdict"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == set(small_cell()["limits"]["limits"])


def test_the_control_is_not_correct(sound):
    """The control's numbers go through the harness's own judge."""
    _, info = sound
    verdict = run.judge(info["numbers"]["control"], small_cell()["limits"])
    assert not all(ok for *_, ok in verdict), verdict
    assert info["control_correct"] is False


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_fault_is_not_correct(fault):
    with faults.FAULTS[fault]():
        line, info = drive()
    assert not line["correct"], info["verdict"]
