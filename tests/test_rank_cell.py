"""The ranking cell (``istella_rank_train``, perfbench) at a size the CPU
holds, through the benchmark's own ``run_cell`` and the program's
interpret seam: ragged queries in length buckets, the plain reference
(perfbench/reference_rank.py) finds the run correct and its int4
control not, a per-query fault is caught, and the layout's gauges are
set.  The configuration is checked against the job it names."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "perfbench")
sys.path.insert(0, os.path.join(BENCH, "tests"))
import faults_rank
import rank_cell


def _config():
    with open(os.path.join(BENCH, "configs",
                           "istella-220-lambdarank.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sound():
    from lightgbm_tpu.telemetry import TELEMETRY
    line, info = rank_cell.drive()
    return line, info, dict(TELEMETRY.gauges())


def test_small_rank_cell_is_correct(sound):
    line, info, _ = sound
    assert line["correct"], info["verdict"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["compared"]) == set(
        rank_cell.small_cell()["limits"]["limits"])
    assert info["numbers"]["leaf_count_mismatch"] == 0


def test_the_control_is_not_correct(sound):
    _, info, _ = sound
    assert info["control_correct"] is False


def test_the_layout_gauges_are_set(sound):
    """The gauges the objective sets when it lays out its queries, and
    the one the cell's metric reads."""
    import datagen_rank
    _, _, gauges = sound
    sizes = datagen_rank.query_sizes(rank_cell.SEED, rank_cell.ROWS)
    assert gauges["objective.rank_max_query"] == sizes.max()
    assert gauges["objective.rank_buckets"] >= 2
    assert 1.0 <= gauges["objective.rank_pair_padding"] < 2.0
    with open(os.path.join(BENCH, "metrics", "rank_pair_padding.json")) as f:
        assert json.load(f)["params"]["gauge"] == "objective.rank_pair_padding"


@pytest.mark.parametrize("fault", ["queries_merged"])
def test_a_per_query_fault_is_not_correct(fault):
    """Queries handed to the program other than the table's.  (At this
    size the quantized sums' noise hides ``query_shifted``, one row a
    query moved: small_limits_rank.json's readings.)"""
    with faults_rank.FAULTS[fault]():
        line, info = rank_cell.drive()
    assert not line["correct"], info["verdict"]


def test_a_program_without_buckets_stops_before_a_tree(monkeypatch):
    """The cell's traffic refuses a program whose lambdarank objective
    sets no bucket gauge (it pads every query to the longest) after
    ``lgb.train(num_boost_round=0)`` and before it grows a tree."""
    import datagen_rank
    import traffic_rank
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.telemetry import TELEMETRY
    X, y, _ = datagen_rank.istella_like(rank_cell.SEED, 3000, 4)
    params = dict(_config()["params"], telemetry="counters")
    ds = lgb.Dataset(X, label=y)
    ds.construct(Config.from_params(params))
    grown = []
    monkeypatch.setattr(TELEMETRY, "gauges", lambda: {})
    real = lgb.train

    def train(params, ds, num_boost_round, **kw):
        grown.append(num_boost_round)
        return real(params, ds, num_boost_round, **kw)
    monkeypatch.setattr(lgb, "train", train)
    with pytest.raises(SystemExit, match="rank_buckets"):
        traffic_rank.train_rank_steady(lgb, None, {"dispatch_chunk": 2},
                                       params, ds, 1.0, None)
    assert grown == [0]


def test_the_configuration_is_the_lambdarank_example():
    """The job of examples/lambdarank/train.conf at its published
    parameters, on a table of the Istella LETOR training split's shape,
    nothing cut; one chip; the cell's own comparison and metrics."""
    import datagen_rank
    import run
    cfg = _config()
    assert len(cfg["source"]) <= 200 and cfg["reduced"] == []
    conf = dict(line.split(" = ") for line in open(os.path.join(
        os.path.dirname(HERE), "examples", "lambdarank", "train.conf"))
        .read().splitlines() if " = " in line and not line.startswith("#"))
    p = cfg["params"]
    assert p["objective"] == conf["objective"] == "lambdarank"
    assert p["num_leaves"] == int(conf["num_leaves"]) == 31
    assert p["learning_rate"] == float(conf["learning_rate"])
    assert (p["max_bin"], p["min_data_in_leaf"], p["max_position"]) \
        == (255, 20, 20)
    assert p["quant_stochastic_rounding"] == 1
    assert (cfg["rows"], cfg["features"]) == (7325625, 220)
    sizes = datagen_rank.query_sizes(cfg["table_seed"], cfg["rows"])
    assert len(sizes) == cfg["published"]["queries"] == 23219
    assert sizes.sum() == cfg["rows"] and sizes.min() >= 2
    assert 200 <= np.median(sizes) <= 260
    loaded = run.load_cell("istella_rank_train")
    assert loaded["cell"]["chips"] == 1
    assert loaded["limits"]["compare"]["kind"] \
        == "reference_rank:gbdt_teacher_forced_rank"
    names = {m["name"] for m in loaded["per_layer"]}
    assert {"rank_objective_ms_per_tree", "rank_lambdas_ms_per_tree",
            "rank_layout_ms_per_tree", "rank_pair_padding"} <= names
