"""``correct`` for the cell over four chips, at a size a test run can
hold: the program on a four-device row mesh (CPU devices, the kernels on
the interpret seam) against ``reference_dp.gbdt_teacher_forced_dp`` on
every device of the process; true for a sound run, false for the control
and for a chip's histogram left out of the sum.  The limits are
small_limits.json's, which this size's one-device cell was given.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest perfbench/tests/test_correct_dp.py -q
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import datagen
import datagen_shards
import faults_dp
import reference
import reference_dp
import roofline_dp
import run

SEED = 2147484001          # above 2**31 - 1, as the driver's can be


def small_cell():
    loaded = run.load_cell("criteo_dp4_train")
    loaded["config"].update(rows=40960,      # 10 x 1024 rows a shard
                            table_seed=SEED)
    loaded["config"]["params"].update(num_leaves=31)
    with open(os.path.join(HERE, "small_limits.json")) as f:
        loaded["limits"] = dict(json.load(f),
                                compare=loaded["limits"]["compare"])
    return loaded


def drive(seed=SEED):
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    return run.run_cell(small_cell(), seed, 0.5, False, jax.devices()[:4],
                        interpret=True)


@pytest.fixture(scope="module")
def sound():
    return drive()


def test_a_sound_run_is_correct(sound):
    line, info = sound
    assert line["correct"], info["verdict"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert info["gauges"]["grower.row_shards"] == 4
    assert info["gauges"]["grower.quantized"] == 1


def test_the_control_is_not_correct(sound):
    _, info = sound
    assert info["control_correct"] is False


def test_a_dropped_shard_is_not_correct():
    with faults_dp.shard_dropped():
        line, info = drive()
    assert not line["correct"], info["verdict"]


def test_the_spread_reference_reads_what_the_one_chip_reference_reads():
    """One answer, one table, both comparisons: the same counts, and
    the same gaps up to the order of the sums over devices.  The table
    goes to the one as a matrix and to the other as the generator's row
    runs, which are the same rows."""
    import lightgbm_tpu as lgb
    import modeltext
    cfg = small_cell()["config"]
    cfg["rows"] = 30000                   # no multiple of anything
    runs, y = datagen_shards.binary_dense_shards(SEED, cfg["rows"],
                                                 cfg["features"])
    X, y1 = datagen.binary_dense(SEED, cfg["rows"], cfg["features"])
    assert np.array_equal(np.concatenate(runs), X) and np.array_equal(y, y1)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 20, "verbose": -1}
    bst = lgb.train(params, lgb.Dataset(X, label=y), 4, verbose_eval=False,
                    keep_training_booster=True)
    answer = {"trees": modeltext.parse(bst.model_to_string()),
              "scores": np.asarray(bst.gbdt.scores)[0, :cfg["rows"]],
              "phase_starts": [0, 2]}
    kw = dict(steps=2, n_sample=6, replay_rows=4096, control_levels=7)
    one = reference.gbdt_teacher_forced(answer, (X, y), cfg, SEED,
                                        reference.binary_logloss, **kw)
    spread = reference_dp.gbdt_teacher_forced_dp(
        answer, (runs, y), cfg, SEED, reference.binary_logloss, **kw)
    assert spread["leaf_count_mismatch"] == one["leaf_count_mismatch"] == 0
    assert spread["score_gap"] == one["score_gap"]
    for name, value in one.items():
        if isinstance(value, float) and name != "score_gap":
            assert spread[name] == pytest.approx(value, rel=0.02, abs=1e-5), name
    for name, value in one["control"].items():
        if name.endswith(("_median", "_p90")):   # one scale a device
            assert spread["control"][name] == pytest.approx(value, rel=0.5), name


def test_exchange_work_by_hand():
    # root + 2 splits = 3 histograms of 67 x 255 x (g, h) int32, 3/4 out
    tree = {"left_child": np.array([1, -1])}
    assert roofline_dp.exchanged_histograms([tree]) == 3
    assert roofline_dp.exchange_bytes_per_chip(67, 255, [tree], 4) == \
        3 * 67 * 255 * 8 * 0.75
    assert roofline_dp.least_exchange_seconds(
        "TPU v5 lite", 67, 255, [tree], 4) == \
        pytest.approx(3 * 67 * 255 * 8 * 0.75 / 200e9)
    with pytest.raises(KeyError):
        roofline_dp.ici_peak("TPU v9")
