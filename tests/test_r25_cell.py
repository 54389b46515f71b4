"""The cell past the int8 ceiling (``criteo_r25_train``, perfbench) at a
size the CPU holds, through the benchmark's own ``run_cell`` and the
program's interpret seam: two row segments, the plain reference finds the
run correct and its int4 control not, and the gauges read what the
configuration expects of the chip's run."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "perfbench")
sys.path.insert(0, os.path.join(BENCH, "tests"))
import rows_cell


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sound():
    with pytest.MonkeyPatch.context() as mp:
        return rows_cell.drive(mp)


def test_small_r25_is_correct_in_two_segments(sound):
    line, info, _ = sound
    assert line["correct"], info["verdict"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["compared"]) == set(
        rows_cell.small_cell()["limits"]["limits"])
    assert info["numbers"]["leaf_count_mismatch"] == 0


def test_the_control_is_not_correct(sound):
    _, info, _ = sound
    assert info["control_correct"] is False


def test_the_gauges_are_what_the_configuration_expects(sound):
    _, info, _ = sound
    gauges = info["gauges"]
    for name, want in _config("criteo-67-r25")["expect_gauges"].items():
        assert gauges[name] == want, name
    assert gauges["grower.hist_segment_rows"] == rows_cell.SEGMENT
    assert gauges["grower.int_counts"] == 1
    assert gauges["grower.hist_kernel"] == "fused_tiled"
    assert gauges["grower.hist_precision"] == "tiered"


def published_rows_a_machine(config):
    return config["published"]["rows"] // config["published"]["machines"]


def test_the_configuration_is_criteo_67_at_twice_the_bound():
    """The published job and every parameter are ``criteo-67``'s; the
    rows are twice what one int32 accumulator sums, and the tier is
    asserted, not switched."""
    from lightgbm_tpu.ops.histogram import QUANT_SEGMENT_ROWS
    r25, base = _config("criteo-67-r25"), _config("criteo-67")
    assert r25["rows"] == 2 * QUANT_SEGMENT_ROWS == 2 * base["rows"]
    for key in ("published", "generator", "features", "reference",
                "reduced"):
        assert r25[key] == base[key], key
    # The same experiment, named down to what defines this deployment:
    # a worker of the source's 16 held ~106M rows, over 2^24.
    assert r25["source"] != base["source"]
    assert r25["source"].split(" (")[0] == base["source"].split(" (")[0]
    assert published_rows_a_machine(r25) > QUANT_SEGMENT_ROWS
    params = dict(r25["params"])
    assert params.pop("hist_precision") == "tiered"
    assert params == base["params"]
    loaded = rows_cell.run.load_cell("criteo_r25_train")
    assert loaded["cell"]["chips"] == 1
    assert loaded["limits"]["compare"]["kind"] \
        == "reference_rows:gbdt_teacher_forced_rows"
    assert "hist_fold_ms_per_tree" in [m["name"] for m in loaded["per_layer"]]


def test_the_update_a_side_at_a_time_is_the_references():
    """``reference_rows.build_update`` on vectors both forms hold: the
    scores to the bit, the loss and the norm to float32's sums."""
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, BENCH)
    import reference
    import reference_rows
    rng = np.random.default_rng(5)
    n, leaves = 3 * 8192, 31
    scores = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, leaves, n), jnp.int32)
    values = jnp.asarray(rng.standard_normal((3, leaves)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    valid = jnp.asarray(np.arange(n) < n - 100, jnp.float32)
    start = jnp.zeros(n, jnp.float32)
    want = reference.build_update(reference.binary_logloss)(
        scores, idx, values, y, valid, start)
    got = reference_rows.build_update(reference.binary_logloss)(
        scores, idx, values, y, valid, start)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)


def test_the_cells_comparison_returns_the_references_numbers(sound):
    """The cell's comparison (three row super-blocks at this size, their
    sums added in float64) against ``reference.gbdt_teacher_forced`` on
    the run both can hold: every number the limits read."""
    sys.path.insert(0, BENCH)
    import reference
    _, info, answer = sound
    loaded = rows_cell.small_cell()
    compare = dict(loaded["limits"]["compare"])
    assert compare.pop("kind") == "reference_rows:gbdt_teacher_forced_rows"
    cfg = loaded["config"]
    data = rows_cell.run.resolve(
        __import__("datagen").GENERATORS, cfg["generator"])(
            rows_cell.SEED, cfg["rows"], cfg["features"])
    want = reference.gbdt_teacher_forced(
        answer, data, cfg, rows_cell.SEED,
        reference.binary_logloss, **compare)
    assert info["numbers"]["leaf_count_mismatch"] \
        == want["leaf_count_mismatch"] == 0
    for numbers, theirs in ((info["numbers"], want),
                            (info["numbers"]["control"], want["control"])):
        for name in loaded["limits"]["limits"]:
            # the float64 sum of float32 parts against one float32 sum:
            # a gap is a difference of two numbers this moves by 1e-7
            assert numbers[name] == pytest.approx(
                theirs[name], rel=0.02, abs=2e-7), name
